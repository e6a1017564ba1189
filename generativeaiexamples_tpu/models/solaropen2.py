"""Solar-Open2-250B (``solar_open2``, 250B-A15B) for the serving engine,
as the share ONE chip holds of an expert-parallel deployment.

One residual stream, pre-norm, every layer routed
(``N(u) = u / sqrt(mean(u^2) + eps) * w``, float32):

- ``h = x + Mix(N1(x))``, ``x' = h + MoE(N2(h))``; a final ``N`` before
  the untied head.
- **Softmax layers** (``gqa_layers``, one of every four): gated GQA, 64
  query / 8 KV heads of 128, NO position term of any kind (``use_rope``
  false) and no per-head norm: ``Mix = W_o [softmax(q k^T / sqrt(128)) v
  * sigmoid(W_g u)]``, the four projections ONE matrix ``wqkvg`` (every
  split on a lane tile), softmax in float32 over every key 0..t.
- **KDA layers** (the other three): Kimi delta attention as
  ``models/glm5next.py`` has it (``_kda_inputs`` / ``_kda_output``, the
  decode step of ``ops/delta_rule.py``: called, not copied) with two
  differences of this model's definition: ``beta = 2 sigmoid(.)``
  (``kda_allow_neg_eigval``: the transition ``I - beta k k^T`` has the
  eigenvalue ``1 - beta`` in (-1, 1) along k) and NO lower bound on the
  log decay. The second decides the block-wise form of prefill and
  extend: ``glm5next.kda_chunk`` divides keys by their cumulative decay
  inside a block (``k e^-G``), safe only under GLM's clamp of -5 a
  token; ``kda_chunk_pairwise`` here takes every decay inside a block
  as ``exp(G_i - G_j)`` a channel with ``i >= j``, never above 1, so no
  ``g <= 0`` can leave float32 (as ``gigachat35.gdn_chunk`` does a
  head). It equals the token-by-token form to float32 rounding.
- **Experts**: ``glm5next.route`` / ``moe`` with scaling 1 and no clamp:
  a sigmoid router over all 320 experts, top 8, of which this chip HOLDS
  ``experts_held`` from ``experts_first`` on, plus the shared expert;
  pairs routed to absent experts are left out
  (``ops/grouped_matmul.py``).

**Two kinds of cache** (docs/model_registry.md). Paged: K and V of each
softmax layer in head-major pages ``[P, Hkv, page, Dh]`` (eight KV heads
are no multiple of the bfloat16 sublane tile), read at decode by
``ops/page_attention.py`` and by the page walk of ``models/afmoe.py`` in
a chunk. Fixed per row: KDA's state ``[rows, H, Dk, Dk]`` float32 and
the convolution's tail ``[rows, conv - 1, 3K]``. ``rows`` is the decode
slots AND, behind them, the rows of the prefix store
(``STATE_ROW_KEYS``, engine/prefix_cache.py): a walk touches the rows
its ``slots`` name (decode: the first ``B``), the engine copies a slot's
row to a store row and back. ``stats`` is a handful of int32 counts of
the last walk.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from generativeaiexamples_tpu.models.afmoe import _attend_pages, _attn_output, _draw, _gqa, _heads_first
from generativeaiexamples_tpu.models.glm5next import MOE_STAT_NAMES, _kda_inputs, _kda_output, _mm, kda_step, moe, rms_norm
from generativeaiexamples_tpu.models.phi4flash import _write_rows
from generativeaiexamples_tpu.ops import delta_rule, page_attention

Params = Dict[str, Any]
Caches = Dict[str, Any]
_HI = lax.Precision.HIGHEST
KDA_BLOCK = 16
BETA_SCALE = 2.0  # kda_allow_neg_eigval

STAT_NAMES = MOE_STAT_NAMES + ("full_tokens_read", "state_kernel_rows")
# the top-level keys of the cache pytree whose leaves hold ONE ROW A SLOT
# (models/registry.py ``state_row_keys``): what a prefix entry carries
STATE_ROW_KEYS = ("kda", "conv")


@dataclasses.dataclass(frozen=True)
class KdaShape:
    """The sizes ``glm5next._kda_inputs`` / ``_kda_output`` read."""

    num_heads: int
    kda_head_dim: int
    kda_rank: int
    kda_conv: int
    norm_eps: float

    @property
    def kda_dim(self) -> int:
        return self.num_heads * self.kda_head_dim


@dataclasses.dataclass(frozen=True)
class SolarOpen2Config:
    """Published widths; ``layers_served`` lists the published layers
    this chip serves (None: all); ``vocab_size``, ``experts_first`` and
    ``experts_held`` are the chip's share."""

    vocab_size: int = 196608
    hidden_size: int = 4096
    intermediate_size: int = 10240  # published; no layer uses it (first_k_dense_replace 0)
    num_hidden_layers: int = 48
    gqa_layers: Tuple[int, ...] = tuple(range(0, 48, 4))
    layers_served: Optional[Tuple[int, ...]] = None
    moe_intermediate_size: int = 1280
    n_routed_experts: int = 320
    num_experts_per_tok: int = 8
    experts_first: int = 0
    experts_held: int = 320
    routed_scaling_factor: float = 1.0
    swiglu_limit: float = math.inf  # no clamp
    num_heads: int = 64
    num_kv_heads: int = 8
    head_dim: int = 128
    kda_num_heads: int = 64
    kda_head_dim: int = 128
    kda_conv: int = 4
    kda_rank: int = 128
    norm_eps: float = 1e-5
    max_seq_len: int = 1048576

    @property
    def layers(self) -> Tuple[str, ...]:
        """The mixer of each layer SERVED: 'full' (softmax) | 'kda'."""
        served = range(self.num_hidden_layers) if self.layers_served is None else self.layers_served
        return tuple("full" if l in self.gqa_layers else "kda" for l in served)

    @property
    def num_layers(self) -> int:
        return len(self.layers)

    @property
    def q_dim(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.head_dim

    @property
    def kda(self) -> KdaShape:
        return KdaShape(self.kda_num_heads, self.kda_head_dim, self.kda_rank, self.kda_conv, self.norm_eps)

    def layers_of(self, mixer: str) -> List[int]:
        return [l for l, m in enumerate(self.layers) if m == mixer]


PRESETS: Dict[str, SolarOpen2Config] = {
    # one chip's share of the 8-way expert-parallel deployment: one whole period
    # (softmax, then three KDA), 40 of 320 experts, an eighth of the vocabulary
    "solar-open2-250b-ep8": SolarOpen2Config(
        vocab_size=24576, layers_served=(0, 1, 2, 3), experts_held=40, max_seq_len=16384),
    # CPU tests: the same period at a size a test checks by hand; 2 of 16 experts
    # held (the first of eight chips), top 4
    "solaropen2-debug": SolarOpen2Config(
        vocab_size=256, hidden_size=64, intermediate_size=96, num_hidden_layers=4, gqa_layers=(0,),
        moe_intermediate_size=32, n_routed_experts=16, num_experts_per_tok=4, experts_held=2,
        num_heads=8, num_kv_heads=2, head_dim=16, kda_num_heads=4, kda_head_dim=16, kda_rank=8,
        max_seq_len=1024,
    ),
}


def validate(cfg: SolarOpen2Config) -> None:
    for l in cfg.layers_served or ():
        if not 0 <= l < cfg.num_hidden_layers:
            raise ValueError(f"layers_served names layer {l} of {cfg.num_hidden_layers}")
    if cfg.experts_first < 0 or cfg.experts_first + cfg.experts_held > cfg.n_routed_experts:
        raise ValueError("the experts held must lie inside the routed experts")
    if cfg.num_heads % cfg.num_kv_heads:
        raise ValueError("every KV head must serve the same number of query heads")


# --------------------------------------------------------------------- //
# Parameters


def _shapes(cfg: SolarOpen2Config, mixer: str) -> Dict[str, Tuple[Tuple[int, ...], str]]:
    """name -> (shape, kind) of one layer's leaves. kind: 'w' a bfloat16
    matrix (std 1/sqrt(fan_in)), 'o' one that writes the stream (scaled
    down by depth), or the name of a float32 leaf whose range
    ``init_params_fast`` gives."""
    D = cfg.hidden_size
    s: Dict[str, Tuple[Tuple[int, ...], str]] = {"ln_mix": ((D,), "near_one"), "ln_mlp": ((D,), "near_one")}
    if mixer == "full":
        # [q | k | v | output gate]
        s.update({"wqkvg": ((D, 2 * cfg.q_dim + 2 * cfg.kv_dim), "w"), "wo": ((cfg.q_dim, D), "o")})
    else:
        kda = cfg.kda
        K, r, H = kda.kda_dim, kda.kda_rank, kda.num_heads
        s.update({
            "wqkv": ((D, 3 * K), "w"), "conv_w": ((kda.kda_conv, 3 * K), "conv"),
            "wbfg": ((D, H + 2 * r), "w"), "wf2": ((r, K), "w"), "wg2": ((r, K), "w"),
            "A_log": ((H,), "A_log"), "dt_bias": ((K,), "dt_bias"),
            "o_norm": ((kda.kda_head_dim,), "near_one"), "wo": ((K, D), "o"),
        })
    F, E = cfg.moe_intermediate_size, cfg.experts_held
    s.update({
        "router": ((D, cfg.n_routed_experts), "router"), "e_bias": ((cfg.n_routed_experts,), "e_bias"),
        "ws_gate_up": ((D, 2 * F), "w"), "ws_down": ((F, D), "o"),
        "we_gate_up": ((E, D, 2 * F), "w"), "we_down": ((E, F, D), "o"),
    })
    return s


def count_logical_params(cfg: SolarOpen2Config) -> int:
    """Parameters this chip HOLDS (its layers, its experts, its vocabulary rows)."""
    n = sum(math.prod(shape) for mixer in cfg.layers for shape, _ in _shapes(cfg, mixer).values())
    return n + 2 * cfg.vocab_size * cfg.hidden_size + cfg.hidden_size


def init_params_fast(cfg: SolarOpen2Config, seed: int = 0, dtype: jnp.dtype = jnp.bfloat16) -> Params:
    """Seeded random weights, drawn leaf by leaf ON the accelerator where
    there is one. Norm weights 1 + N(0, 0.1) (a dropped norm is not
    hidden), ``e_bias`` N(0, 0.01) (the tie-break is exercised),
    ``A_log`` = log U(1, 16), ``dt_bias`` so that softplus gives steps
    log-uniform in 1e-3..1e-1 (``models/glm5next.py``'s ranges)."""
    validate(cfg)
    out_scale = 1.0 / math.sqrt(2 * cfg.num_layers)
    root = jax.random.key(seed, impl="rbg")  # the generator the chip has in hardware
    counter = [0]

    def key():
        counter[0] += 1
        return jax.random.fold_in(root, counter[0])

    def normal(shape, std, dt=dtype, mean=0.0):
        return _draw(key(), tuple(shape), float(std), float(mean), jnp.dtype(dt).name)

    def leaf(shape, kind):
        if kind == "w":
            return normal(shape, 1 / math.sqrt(shape[-2]))
        if kind == "o":
            return normal(shape, out_scale / math.sqrt(shape[-2]))
        if kind == "conv":
            return normal(shape, 1 / math.sqrt(shape[0]), jnp.float32)
        if kind == "router":
            return normal(shape, 1 / math.sqrt(shape[0]), jnp.float32)
        if kind == "near_one":
            return normal(shape, 0.1, jnp.float32, mean=1.0)
        if kind == "e_bias":
            return normal(shape, 0.01, jnp.float32)
        if kind == "A_log":
            return jnp.log(jax.random.uniform(key(), shape, jnp.float32, 1.0, 16.0))
        if kind == "dt_bias":
            dt0 = jnp.exp(jax.random.uniform(key(), shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
            return dt0 + jnp.log(-jnp.expm1(-dt0))  # softplus^-1
        raise ValueError(kind)

    with jax.default_device(jax.devices()[0]):  # the accelerator where there is one
        layers = [{name: leaf(shape, kind) for name, (shape, kind) in _shapes(cfg, mixer).items()}
                  for mixer in cfg.layers]
        D = cfg.hidden_size
        return {
            "embed": normal((cfg.vocab_size, D), 1 / math.sqrt(D)),
            "head": normal((D, cfg.vocab_size), 1 / math.sqrt(D)),
            "final_norm": leaf((D,), "near_one"),
            "layers": layers,
        }


# --------------------------------------------------------------------- //
# Caches and the memory plan


def init_paged_cache(cfg: SolarOpen2Config, pool_pages: int, page_size: int, num_rows: int,
                     dtype: jnp.dtype = jnp.bfloat16) -> Caches:
    """``num_rows``: the decode slots and, behind them, the prefix store's rows."""
    kda = cfg.kda
    page = (pool_pages, cfg.num_kv_heads, page_size, cfg.head_dim)  # head-major pages
    n_kda = len(cfg.layers_of("kda"))
    return {
        "full": [{"k": jnp.zeros(page, dtype), "v": jnp.zeros(page, dtype)} for _ in cfg.layers_of("full")],
        "kda": [jnp.zeros((num_rows, kda.num_heads, kda.kda_head_dim, kda.kda_head_dim), jnp.float32)
                for _ in range(n_kda)],
        "conv": [jnp.zeros((num_rows, kda.kda_conv - 1, 3 * kda.kda_dim), dtype) for _ in range(n_kda)],
        "stats": jnp.zeros((len(STAT_NAMES),), jnp.int32),
    }


def kv_bytes_per_token(cfg: SolarOpen2Config, kv_bytes: float = 2) -> int:
    """Paged bytes a cached token costs: K and V of each softmax layer."""
    return int(len(cfg.layers_of("full")) * 2 * cfg.kv_dim * kv_bytes)


def fixed_state_bytes_per_slot(cfg: SolarOpen2Config, kv_bytes: float = 2) -> int:
    """Bytes a row holds whatever its sequence length: KDA's state and the convolution's tail."""
    kda = cfg.kda
    per = kda.num_heads * kda.kda_head_dim ** 2 * 4 + (kda.kda_conv - 1) * 3 * kda.kda_dim * kv_bytes
    return int(len(cfg.layers_of("kda")) * per)


def serving_memory_bytes(cfg: SolarOpen2Config, batch: int, max_seq_len: int,
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


def kda_chunk_pairwise(S, q, k, v, beta, g, block: int = KDA_BLOCK):
    """The delta rule with per-channel decay over T tokens, block-wise
    (WY / UT transform), safe for ANY ``g <= 0``. S [N, H, Dk, Dv]; q, k,
    g [N, T, H, Dk]; v [N, T, H, Dv]; beta [N, T, H]. A token with beta =
    0 and g = 0 leaves the state as it is. Returns (o [N, T, H, Dv], S).

    Inside a block, with ``G`` the cumulative log decay from the block's
    start and ``L_ij = e^(G_i - G_j)`` a channel for ``i >= j`` (never
    above 1; 0 above the diagonal): ``A = strict_tril(beta sum_d k_i k_j
    L_ij)``, ``T = (I + A)^-1``, ``U = T beta v - T (beta k e^G) S``,
    ``O = (q e^G) S + tril(sum_d q_i k_j L_ij) U``,
    ``S <- e^G_end S + (k e^(G_end - G))^T U``. Every exponent is a
    difference of a later and an earlier cumulative decay: <= 0."""
    N, T, H, Dk = q.shape
    B = min(block, T)
    nb = T // B
    assert nb * B == T, (T, B)

    def blocks(x):  # [N, T, H, D] -> [nb, N, H, B, D]
        return jnp.transpose(x.reshape(N, nb, B, H, x.shape[-1]), (1, 0, 3, 2, 4))

    qb, kb, vb, gb = blocks(q), blocks(k), blocks(v), blocks(g)
    bb = jnp.transpose(beta.reshape(N, nb, B, H), (1, 0, 3, 2))  # [nb, N, H, B]
    G = jnp.cumsum(gb, axis=-2)  # [nb, N, H, B, Dk]
    tril = jnp.tril(jnp.ones((B, B), bool))
    # L [.., i, j, d]; the exponent is masked BEFORE exp: above the diagonal it would be positive
    L = jnp.exp(jnp.where(tril[:, :, None], G[..., :, None, :] - G[..., None, :, :], -jnp.inf))
    kL = kb[..., None, :, :] * L  # [.., i, j, d] = k_j[d] L_ij[d]
    A = jnp.sum(kb[..., :, None, :] * kL, axis=-1) * bb[..., None]
    A = jnp.where(jnp.tril(tril, -1), A, 0.0)
    Pq = jnp.sum(qb[..., :, None, :] * kL, axis=-1)  # 0 above the diagonal (L is)
    # (I + A)^-1 = (I - A)(I + A^2)(I + A^4)...: A is strictly lower, so A^B = 0
    eye = jnp.eye(B, dtype=jnp.float32)
    Tm, P = eye - A, jnp.matmul(A, A, precision=_HI)
    for _ in range(max(0, (B - 1).bit_length() - 1)):
        Tm = jnp.matmul(Tm, eye + P, precision=_HI)
        P = jnp.matmul(P, P, precision=_HI)
    eG = jnp.exp(G)
    Wv = jnp.matmul(Tm, bb[..., None] * vb, precision=_HI)
    Wk = jnp.matmul(Tm, bb[..., None] * kb * eG, precision=_HI)
    qg = qb * eG
    k_end = kb * jnp.exp(G[..., -1:, :] - G)
    g_end = eG[..., -1, :]  # [nb, N, H, Dk]

    def body(S, xs):
        Wv_b, Wk_b, qg_b, Pq_b, ke_b, ge_b = xs
        U = Wv_b - jnp.matmul(Wk_b, S, precision=_HI)
        O = jnp.matmul(qg_b, S, precision=_HI) + jnp.matmul(Pq_b, U, precision=_HI)
        S = ge_b[..., None] * S + jnp.einsum("...tk,...tv->...kv", ke_b, U, precision=_HI)
        return S, O

    S, O = lax.scan(body, S, (Wv, Wk, qg, Pq, k_end, g_end))
    O = jnp.transpose(O, (1, 0, 3, 2, 4))  # [nb, N, H, B, Dv] -> [N, nb, B, H, Dv]
    return O.reshape(N, T, H, O.shape[-1]), S


def _project(u, lp: Params, cfg: SolarOpen2Config, dtype):
    """The normed input u [N, T, D] -> q [N, T, Hq, Dh], k, v
    [N, T, Hkv, Dh] in ``dtype`` (what the pages hold and the score
    product multiplies) and the output gate [N, T, Hq * Dh] float32.
    Nothing is normed per head, nothing rotated."""
    Hq, Hk, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    qkvg = _mm(u, lp["wqkvg"])
    q, k, v, g = jnp.split(qkvg, [cfg.q_dim, cfg.q_dim + cfg.kv_dim, cfg.q_dim + 2 * cfg.kv_dim], axis=-1)
    heads = lambda x, h: x.reshape(x.shape[:-1] + (h, Dh)).astype(dtype)  # noqa: E731
    return heads(q, Hq), heads(k, Hk), heads(v, Hk), jax.nn.sigmoid(g)


def _kda(x, cat, lp: Params, cfg: SolarOpen2Config):
    return _kda_inputs(x, cat, lp, cfg.kda, beta_scale=BETA_SCALE, lower_bound=None)


def _moe(h, lp: Params, cfg: SolarOpen2Config, count, kernel: Optional[str]):
    """``MoE(N2(h))`` over h [.., D] float32; returns (output, stats [4])."""
    u = rms_norm(h, lp["ln_mlp"], cfg.norm_eps, jnp.float32)
    # the router scores the float32 row; the experts multiply it in the weights' dtype
    y, stats = moe(u.reshape(-1, u.shape[-1]), lp, cfg, count.reshape(-1), kernel,
                   expert_dtype=lp["we_gate_up"].dtype)
    return y.reshape(h.shape), stats


def head(params: Params, cfg: SolarOpen2Config, hidden):
    """hidden [N, D] -> float32 logits [N, V]."""
    return _mm(rms_norm(hidden, params["final_norm"], cfg.norm_eps, jnp.float32), params["head"])


def _embed(params: Params, tokens):
    return params["embed"][tokens].astype(jnp.float32)


# --------------------------------------------------------------------- //
# The chunk walk: prefill and chunked extend


def _chunk_walk(params: Params, cfg: SolarOpen2Config, caches: Caches, tokens, offsets, valid, slots,
                tables, page_size: int, fresh: bool, grouped_matmul: Optional[str] = None):
    """All layers over a chunk [N, C] per row; returns (the residual row
    of each row's last valid position [N, D], caches).

    A KDA layer's row at ``offsets == 0`` starts from a zero state and a
    zero tail (which is what resets a slot at admission); a row at
    ``offsets > 0`` carries its slot's state on: the last chunk's, or
    the one the engine restored from the prefix store. A row with
    ``valid == 0`` changes nothing. The softmax layer writes its pages
    and (``fresh`` False) walks each row's own pages as far as its
    context reaches whatever window the engine names: one program a
    chunk width."""
    N, C = tokens.shape
    S = tables.shape[1] * page_size
    idx = jnp.arange(C, dtype=jnp.int32)
    positions = jnp.minimum(offsets[:, None] + idx[None, :], S - 1)  # [N, C]
    tok_valid = idx[None, :] < valid[:, None]
    row_live = valid > 0
    started = row_live & (offsets > 0)
    last = jnp.clip(valid, 1, C) - 1
    row_tables = tables[slots]
    causal = positions[:, :, None] >= positions[:, None, :]  # [N, C, C] chunk keys
    full_read = jnp.zeros((), jnp.int32)
    moe_stats = jnp.zeros((len(MOE_STAT_NAMES),), jnp.int32)
    kda = cfg.kda

    x = _embed(params, tokens)  # [N, C, D]
    new = {k: list(v) if isinstance(v, list) else v for k, v in caches.items()}
    dtype = params["embed"].dtype
    i_kda = i_full = 0
    for l, mixer in enumerate(cfg.layers):
        lp = params["layers"][l]
        u = rms_norm(x, lp["ln_mix"], cfg.norm_eps, jnp.float32)
        if mixer == "full":
            i, i_full = i_full, i_full + 1
            with jax.named_scope("full_attn"):
                q, k, v, gate = _project(u, lp, cfg, dtype)
                old = caches["full"][i]
                phys = jnp.take_along_axis(row_tables, positions // page_size, axis=1)
                phys = jnp.where(tok_valid, phys, old["k"].shape[0])  # padding: dropped
                sip = positions % page_size
                pool = {"k": _write_rows(old["k"], phys, sip, k), "v": _write_rows(old["v"], phys, sip, v)}
                new["full"][i] = pool
                full_read = full_read + jnp.sum(jnp.where(tok_valid, positions + 1, 0))
                if fresh:
                    o = _gqa(q, _heads_first(k), _heads_first(v), causal)
                else:
                    o = _attend_pages(q, pool, row_tables, positions, jnp.where(row_live, offsets + valid, 0))
                mixed = _attn_output(o, gate, lp)
        else:
            i, i_kda = i_kda, i_kda + 1
            with jax.named_scope("kda_chunk"):
                old_S, old_tail = caches["kda"][i][slots], caches["conv"][i][slots]
                proj = _mm(u, lp["wqkv"])
                tail = jnp.where(started[:, None, None], old_tail.astype(jnp.float32), 0.0)
                cat = jnp.concatenate([tail, proj], axis=1)
                q, k, v, beta, g, gate = _kda(u, cat, lp, cfg)
                beta = jnp.where(tok_valid[..., None], beta, 0.0)
                g = jnp.where(tok_valid[..., None, None], g, 0.0)
                S0 = jnp.where(started[:, None, None, None], old_S, 0.0).astype(jnp.float32)
                o, S1 = kda_chunk_pairwise(S0, q, k, v, beta, g)
                taps = valid[:, None] + jnp.arange(kda.kda_conv - 1, dtype=jnp.int32)[None, :]
                new_tail = jnp.take_along_axis(cat, taps[:, :, None], axis=1).astype(old_tail.dtype)
                keep = row_live[:, None, None]
                new["kda"][i] = caches["kda"][i].at[slots].set(
                    jnp.where(keep[..., None], S1.astype(old_S.dtype), old_S))
                new["conv"][i] = caches["conv"][i].at[slots].set(jnp.where(keep, new_tail, old_tail))
                mixed = _kda_output(o, gate, lp, kda)
        h = x + mixed
        y, stats = _moe(h, lp, cfg, tok_valid, grouped_matmul)
        x = h + y
        moe_stats = moe_stats + stats
    # the block-wise recurrence advanced every state: the step kernel none
    new["stats"] = jnp.concatenate([moe_stats, jnp.stack([full_read, jnp.zeros((), jnp.int32)])]).astype(jnp.int32)
    return jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0], new


def prefill_paged(params: Params, cfg: SolarOpen2Config, caches: Caches, tokens, lengths, slots, tables,
                  page_size: int, grouped_matmul: Optional[str] = None, **_paths):
    """A monolithic admission wave: (last-position logits [N, V], caches)."""
    hidden, caches = _chunk_walk(params, cfg, caches, tokens, jnp.zeros_like(lengths), lengths, slots,
                                 tables, page_size, True, grouped_matmul)
    return head(params, cfg, hidden), caches


def extend_paged(params: Params, cfg: SolarOpen2Config, caches: Caches, tokens, offsets, valid, slots,
                 tables, window: int, page_size: int, grouped_matmul: Optional[str] = None, **_paths):
    """One chunk of a chunked prefill: (the residual row [N, D] of each
    row's last valid position, caches)."""
    del window  # the softmax layer's read follows each row's own context
    return _chunk_walk(params, cfg, caches, tokens, offsets, valid, slots, tables, page_size, False,
                       grouped_matmul)


# --------------------------------------------------------------------- //
# One decode step


def decode_paged(params: Params, cfg: SolarOpen2Config, caches: Caches, tokens, positions, live, tables,
                 window: Optional[int], page_size: int, page_kernel: Optional[str] = None,
                 grouped_matmul: Optional[str] = None, delta_step: Optional[str] = None, **_paths):
    """One token per slot: (logits [B, V], caches). The fixed-state
    leaves may hold more rows than the ``B`` slots (the prefix store's,
    behind them): the step reads and writes the first ``B`` and leaves
    the rest as they are. A dead row (``live`` False; the engine has
    zeroed its position) keeps its state and writes nothing to the pool.
    ``delta_step`` ('compiled' / 'interpret') advances KDA's state with
    ``ops/delta_rule.py``, in place; None with ``kda_step``."""
    del window
    B = tokens.shape[0]
    S = tables.shape[1] * page_size
    pos2 = positions[:, None]
    sip = pos2 % page_size
    work = page_attention.page_work_list(
        tables, positions, 1, page_size, page_attention.pages_per_step(caches["full"][0]["k"])
    ) if page_kernel and caches["full"] else None
    full_read = jnp.zeros((), jnp.int32)
    moe_stats = jnp.zeros((len(MOE_STAT_NAMES),), jnp.int32)
    kda = cfg.kda

    x = _embed(params, tokens[:, None])  # [B, 1, D]
    new = {k: list(v) if isinstance(v, list) else v for k, v in caches.items()}
    dtype = params["embed"].dtype
    i_kda = i_full = 0
    for l, mixer in enumerate(cfg.layers):
        lp = params["layers"][l]
        u = rms_norm(x, lp["ln_mix"], cfg.norm_eps, jnp.float32)
        if mixer == "full":
            i, i_full = i_full, i_full + 1
            with jax.named_scope("full_attn"):
                q, k, v, gate = _project(u, lp, cfg, dtype)
                old = caches["full"][i]
                phys = jnp.where(live[:, None], jnp.take_along_axis(tables, pos2 // page_size, axis=1),
                                 old["k"].shape[0])
                pool = {"k": _write_rows(old["k"], phys, sip, k), "v": _write_rows(old["v"], phys, sip, v)}
                new["full"][i] = pool
                full_read = full_read + jnp.sum(jnp.where(live, positions + 1, 0))
                if page_kernel:
                    o = page_attention.paged_attention(
                        q, pool["k"], pool["v"], tables, positions,
                        interpret=(page_kernel == "interpret"), work=work, head_major=True)
                else:
                    gk, gv = (jnp.moveaxis(buf[tables], 1, 2).reshape(B, cfg.num_kv_heads, S, cfg.head_dim)
                              for buf in (pool["k"], pool["v"]))
                    o = _gqa(q, gk, gv, jnp.arange(S, dtype=jnp.int32)[None, None, :] <= pos2[:, :, None])
                mixed = _attn_output(o.astype(jnp.float32), gate, lp)
        else:
            i, i_kda = i_kda, i_kda + 1
            with jax.named_scope("kda_step"):
                all_S, all_tail = caches["kda"][i], caches["conv"][i]
                old_tail = all_tail[:B]
                proj = _mm(u[:, 0], lp["wqkv"])
                cat = jnp.concatenate([old_tail.astype(jnp.float32), proj[:, None]], axis=1)
                q, k, v, beta, g, gate = _kda(u, cat, lp, cfg)
                step = (q[:, 0], k[:, 0], v[:, 0], beta[:, 0], g[:, 0])
                keep = live[:, None, None]
                if delta_step:
                    # the kernel's grid walks the first B rows of the state and writes in place
                    o, new["kda"][i] = delta_rule.delta_rule_step(
                        all_S, *step, live, interpret=(delta_step == "interpret"))
                else:
                    old_S = all_S[:B]
                    o, S1 = kda_step(old_S.astype(jnp.float32), *step)
                    new["kda"][i] = all_S.at[:B].set(jnp.where(keep[..., None], S1.astype(all_S.dtype), old_S))
                new["conv"][i] = all_tail.at[:B].set(jnp.where(keep, cat[:, 1:].astype(all_tail.dtype), old_tail))
                mixed = _kda_output(o, gate[:, 0], lp, kda)[:, None]
        h = x + mixed
        y, stats = _moe(h, lp, cfg, live[:, None], grouped_matmul)
        x = h + y
        moe_stats = moe_stats + stats
    kernel_rows = jnp.sum(live.astype(jnp.int32)) if delta_step and i_kda else jnp.zeros((), jnp.int32)
    new["stats"] = jnp.concatenate([moe_stats, jnp.stack([full_read, kernel_rows])]).astype(jnp.int32)
    return head(params, cfg, x[:, 0]), new


# --------------------------------------------------------------------- //
# The whole sequence at once, token by token, no cache: what the tests
# hold the paged walks against (the plain reference of the benchmark is
# perfbench/arch/solaropen2.py and imports nothing from here)


def forward_full(params: Params, cfg: SolarOpen2Config, tokens):
    """Logits [N, T, V] of tokens [N, T]: KDA as a token-by-token scan of
    ``kda_step``, the softmax layers with the causal mask whole, the
    experts densely over the held ones."""
    N, T = tokens.shape
    idx = jnp.arange(T, dtype=jnp.int32)
    causal = jnp.broadcast_to((idx[:, None] >= idx[None, :])[None], (N, T, T))
    everyone = jnp.ones((N, T), bool)
    kda = cfg.kda
    dtype = params["embed"].dtype
    x = _embed(params, tokens)
    for l, mixer in enumerate(cfg.layers):
        lp = params["layers"][l]
        u = rms_norm(x, lp["ln_mix"], cfg.norm_eps, jnp.float32)
        if mixer == "full":
            q, k, v, gate = _project(u, lp, cfg, dtype)
            mixed = _attn_output(_gqa(q, _heads_first(k), _heads_first(v), causal), gate, lp)
        else:
            proj = _mm(u, lp["wqkv"])
            cat = jnp.pad(proj, ((0, 0), (kda.kda_conv - 1, 0), (0, 0)))
            q, k, v, beta, g, gate = _kda(u, cat, lp, cfg)

            def step(S, xs):
                o, S = kda_step(S, *xs)
                return S, o

            S0 = jnp.zeros((N, kda.num_heads, kda.kda_head_dim, kda.kda_head_dim), jnp.float32)
            _, o = lax.scan(step, S0, tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, beta, g)))
            mixed = _kda_output(jnp.moveaxis(o, 0, 1), gate, lp, kda)
        h = x + mixed
        y, _ = _moe(h, lp, cfg, everyone, None)
        x = h + y
    return head(params, cfg, x.reshape(N * T, -1)).reshape(N, T, -1)
