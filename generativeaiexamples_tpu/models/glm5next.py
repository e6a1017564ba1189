"""GLM-5.3-Flash (``glm5_next_text``) for the serving engine, as the share
ONE chip holds of an expert-parallel deployment.

Four mechanisms, each with its equations in ISSUE 35 / the benchmark's
reference (``perfbench/arch/glm5next.py``):

- **mHC residual.** The stream between layers is ``X [n, D]`` (``n = 4``
  copies of the hidden state). Each sublayer ``F`` (mixer, MLP) reads
  ``RMSNorm(Hpre X)`` and writes ``X <- Hres X + Hpost^T F(.)`` with
  ``Hpre, Hpost`` sigmoid gates and ``Hres`` a 4 x 4 matrix made doubly
  stochastic by 20 Sinkhorn sweeps, all three computed per token from
  ``RMSNorm(vec(X))``. float32.
- **KDA** (Kimi delta attention, arXiv:2510.26692): 64 heads, a
  ``[128, 128]`` float32 state a head, per-channel decay. Decode is one
  delta-rule step a token (``ops/delta_rule.py`` where the ``delta_step``
  path resolved: the state read once and written once, in place;
  ``kda_step`` elsewhere); prefill and extend compute the same
  recurrence block-wise in the WY / UT-transform form (``kda_chunk``):
  blocks of 16 tokens, matrix products, a 32-step loop over the blocks
  of a 512-token chunk carrying the state. 16, not 64: the form divides
  keys by their cumulative decay, and ``gate_lower_bound`` -5 x 16 = 80
  keeps ``exp`` inside float32 where 64 would need a second level.
- **Sparse latent attention** (MLA, no position encoding, absorbed): the
  cache holds ONE 512-wide latent row a token, key and value of all 64
  heads. A learned indexer (32 heads of 128, RoPE) scores mean-pooled
  keys of aligned 4-token groups and keeps the 512 best groups (2048
  tokens) plus the query's own open group. The selection reaches the
  read as a mask: at the contexts one chip serves, every live page is
  read once (``ops/latent_attention.py``).
- **Experts**: a sigmoid router over all 288 experts, top 8, of which
  this chip HOLDS ``experts_held`` from ``experts_first`` on, plus the
  shared expert. What the absent experts would add is left out — the
  partial sum goes on, as it would into the deployment's exchange
  (``ops/grouped_matmul.py``).

**Two kinds of cache** (docs/model_registry.md). Paged: the latent rows
``lat [P, page, R]`` and the pooled index keys ``idx [P, page/4, Di]``
(one 128-vector a complete group) of each sparse-attention layer. Fixed
per slot: KDA's state ``[slots, H, Dk, Dv]`` float32 and the three
convolution tails, and the running sum of the open group's index keys.
``stats`` is a handful of int32 counts of the last walk (pairs held,
experts hit, tokens selected, rows the step kernel advanced), which the
engine reads back with the tokens.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from generativeaiexamples_tpu.ops import delta_rule
from generativeaiexamples_tpu.ops import grouped_matmul as expert_ops
from generativeaiexamples_tpu.ops import latent_attention

Params = Dict[str, Any]
Caches = Dict[str, Any]
_HI = lax.Precision.HIGHEST
_NEG = -1e30
KDA_BLOCK = 16

# what ``moe`` counts, in the order it returns them: every expert family's STAT_NAMES starts with these
MOE_STAT_NAMES = ("moe_pairs_held", "moe_pairs_absent", "moe_experts_hit", "moe_experts_held",
                  "moe_tiles_used", "moe_tiles_planned")
STAT_NAMES = MOE_STAT_NAMES + ("dsa_tokens_selected", "dsa_context_tokens", "state_kernel_rows")


@dataclasses.dataclass(frozen=True)
class Glm5NextConfig:
    """Published widths; ``layers`` lists (mixer, mlp) of the layers
    served; ``vocab_size``, ``experts_first`` and ``experts_held`` are
    this chip's share."""

    vocab_size: int = 154880
    hidden_size: int = 4096
    layers: Tuple[Tuple[str, str], ...] = (("kda", "dense"),) * 3 + (
        ("dsa", "sparse"), ("kda", "sparse"), ("kda", "sparse"), ("kda", "sparse"))
    intermediate_size: int = 12288
    moe_intermediate_size: int = 2048
    n_routed_experts: int = 288
    num_experts_per_tok: int = 8
    experts_first: int = 0
    experts_held: int = 288
    routed_scaling_factor: float = 2.5
    swiglu_limit: float = 10.0
    num_heads: int = 64
    kda_head_dim: int = 128
    kda_conv: int = 4
    kda_rank: int = 128
    gate_lower_bound: float = -5.0
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_head_dim: int = 256
    v_head_dim: int = 256
    index_n_heads: int = 32
    index_head_dim: int = 128
    index_rope_dim: int = 64
    index_topk: int = 2048
    index_kpool: int = 4
    rope_theta: float = 10000.0
    hc_mult: int = 4
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    norm_eps: float = 1e-5
    max_seq_len: int = 1048576

    @property
    def num_layers(self) -> int:
        return len(self.layers)

    @property
    def kda_dim(self) -> int:
        return self.num_heads * self.kda_head_dim

    @property
    def topk_groups(self) -> int:
        return self.index_topk // self.index_kpool

    def layers_of(self, mixer: str):
        return [l for l, (m, _) in enumerate(self.layers) if m == mixer]

    def sparse_layers(self):
        return [l for l, (_, f) in enumerate(self.layers) if f == "sparse"]


_PERIOD = (("kda", "dense"), ("dsa", "sparse"), ("kda", "sparse"), ("kda", "sparse"), ("kda", "sparse"))

PRESETS: Dict[str, Glm5NextConfig] = {
    # one chip's share of the 8-way expert-parallel deployment: published
    # layer 0 and the period 3-6, 36 of 288 experts, an eighth of the vocabulary
    "glm-5.3-flash-ep8": Glm5NextConfig(
        vocab_size=19360, layers=_PERIOD, experts_held=36, max_seq_len=8192),
    # CPU tests: the same five layers at a size a test checks by hand;
    # top-8 groups of 4, 2 of 16 experts held (the first of eight chips)
    "glm5next-debug": Glm5NextConfig(
        vocab_size=256, hidden_size=64, layers=_PERIOD, intermediate_size=96,
        moe_intermediate_size=32, n_routed_experts=16, num_experts_per_tok=4, experts_held=2,
        num_heads=4, kda_head_dim=16, kda_rank=8, q_lora_rank=48, kv_lora_rank=32,
        qk_head_dim=16, v_head_dim=16, index_n_heads=2, index_head_dim=16, index_rope_dim=8,
        index_topk=32, max_seq_len=1024,
    ),
}


def validate(cfg: Glm5NextConfig) -> None:
    for mixer, mlp in cfg.layers:
        if mixer not in ("kda", "dsa") or mlp not in ("dense", "sparse"):
            raise ValueError(f"unknown layer kinds {(mixer, mlp)}")
    if cfg.experts_first < 0 or cfg.experts_first + cfg.experts_held > cfg.n_routed_experts:
        raise ValueError("the experts held must lie inside the routed experts")
    if cfg.index_topk % cfg.index_kpool:
        raise ValueError("index_topk must be whole groups of index_kpool tokens")


# --------------------------------------------------------------------- //
# Parameters


def _shapes(cfg: Glm5NextConfig, mixer: str, mlp: str) -> Dict[str, Tuple[Tuple[int, ...], str]]:
    """name -> (shape, kind) of one layer's leaves. kind: 'w' a bfloat16
    matrix (std 1/sqrt(fan_in)), 'o' one that writes the stream (scaled
    down by depth), 'one' a norm weight, or the name of a float32 leaf
    whose range ``init_params_fast`` gives."""
    D, H = cfg.hidden_size, cfg.num_heads
    n = cfg.hc_mult
    s: Dict[str, Tuple[Tuple[int, ...], str]] = {}
    for sub in ("mix", "mlp"):
        s[f"hc_{sub}_phi"] = ((n * D, 2 * n + n * n), "phi")
        s[f"hc_{sub}_norm"] = ((n * D,), "one32")
        s[f"hc_{sub}_a"] = ((3,), "hc_a")
        s[f"hc_{sub}_b"] = ((2 * n + n * n,), "hc_b")
        s[f"ln_{sub}"] = ((D,), "one")
    if mixer == "kda":
        K, r = cfg.kda_dim, cfg.kda_rank
        s.update({
            "wqkv": ((D, 3 * K), "w"), "conv_w": ((cfg.kda_conv, 3 * K), "conv"),
            "wbfg": ((D, H + 2 * r), "w"), "wf2": ((r, K), "w"), "wg2": ((r, K), "w"),
            "A_log": ((H,), "A_log"), "dt_bias": ((K,), "dt_bias"),
            "o_norm": ((cfg.kda_head_dim,), "one"), "wo": ((K, D), "o"),
        })
    else:
        ql, kl, Di = cfg.q_lora_rank, cfg.kv_lora_rank, cfg.index_head_dim
        s.update({
            "wx": ((D, ql + kl + Di + cfg.index_n_heads), "w"),
            "q_norm": ((ql,), "one"), "kv_norm": ((kl,), "one"),
            "ki_norm_w": ((Di,), "one"), "ki_norm_b": ((Di,), "zero"),
            "wcq": ((ql, H * cfg.qk_head_dim + cfg.index_n_heads * Di), "w"),
            "wuk": ((H, cfg.qk_head_dim, kl), "wuk"), "wuv": ((H, kl, cfg.v_head_dim), "wuv"),
            "wo": ((H * cfg.v_head_dim, D), "o"),
        })
    if mlp == "dense":
        F = cfg.intermediate_size
        s.update({"w_gate_up": ((D, 2 * F), "w"), "w_down": ((F, D), "o")})
    else:
        F, E = cfg.moe_intermediate_size, cfg.experts_held
        s.update({
            "router": ((D, cfg.n_routed_experts), "router"), "e_bias": ((cfg.n_routed_experts,), "e_bias"),
            "ws_gate_up": ((D, 2 * F), "w"), "ws_down": ((F, D), "o"),
            "we_gate_up": ((E, D, 2 * F), "we"), "we_down": ((E, F, D), "we_o"),
        })
    return s


def count_logical_params(cfg: Glm5NextConfig) -> int:
    """Parameters this chip HOLDS (its experts, its vocabulary rows)."""
    n = sum(math.prod(shape) for mixer, mlp in cfg.layers for shape, _ in _shapes(cfg, mixer, mlp).values())
    return n + 2 * cfg.vocab_size * cfg.hidden_size + cfg.hidden_size


def init_params_fast(cfg: Glm5NextConfig, seed: int = 0, dtype: jnp.dtype = jnp.bfloat16) -> Params:
    """Seeded random weights, drawn leaf by leaf ON the accelerator where
    there is one (4.7 B parameters on one host core would take minutes;
    the engine stages other families on the host because it quantises
    them there). Ranges of the float32 leaves: ``A_log`` = log U(1, 16);
    ``dt_bias`` so that softplus gives steps log-uniform in 1e-3..1e-1;
    ``e_bias`` N(0, 0.01); the mHC scalars ``a`` = 0.01, ``b_pre`` 0
    (each stream weighted 1/2), ``b_post`` 0 (1), ``b_res`` 4 on the
    diagonal and -4 off it, so ``Hres`` starts near the identity."""
    validate(cfg)
    n, L = cfg.hc_mult, cfg.num_layers
    out_scale = 1.0 / math.sqrt(2 * L)
    # the generator the chip has in hardware: threefry would spend a minute
    # on 4.7 B draws (what it yields depends on the backend; the seed fixes
    # it on one, and the benchmark's reference reads the engine's weights)
    root = jax.random.key(seed, impl="rbg")
    counter = [0]

    def key():
        counter[0] += 1
        return jax.random.fold_in(root, counter[0])

    def normal(shape, std, dt=dtype):
        return _draw(key(), tuple(shape), float(std), jnp.dtype(dt).name)

    def leaf(shape, kind):
        if kind in ("w", "we"):
            return normal(shape, 1 / math.sqrt(shape[-2]))
        if kind in ("o", "we_o"):
            return normal(shape, out_scale / math.sqrt(shape[-2]))
        if kind in ("wuk", "wuv"):  # [H, in, out]
            return normal(shape, 1 / math.sqrt(shape[1]))
        if kind == "conv":
            return normal(shape, 1 / math.sqrt(shape[0]), jnp.float32)
        if kind == "one":
            return jnp.ones(shape, dtype)
        if kind == "one32":
            return jnp.ones(shape, jnp.float32)
        if kind == "zero":
            return jnp.zeros(shape, dtype)
        if kind in ("phi", "router"):
            return normal(shape, 1 / math.sqrt(shape[0]), jnp.float32)
        if kind == "e_bias":
            return normal(shape, 0.01, jnp.float32)
        if kind == "hc_a":
            return jnp.full(shape, 0.01, jnp.float32)
        if kind == "hc_b":
            eye = jnp.where(jnp.eye(n, dtype=bool), 4.0, -4.0).reshape(-1)
            return jnp.concatenate([jnp.zeros((2 * n,), jnp.float32), eye.astype(jnp.float32)])
        if kind == "A_log":
            return jnp.log(jax.random.uniform(key(), shape, jnp.float32, 1.0, 16.0))
        if kind == "dt_bias":
            dt0 = jnp.exp(jax.random.uniform(key(), shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
            return dt0 + jnp.log(-jnp.expm1(-dt0))  # softplus^-1
        raise ValueError(kind)

    device = jax.devices()[0]  # the accelerator where there is one
    with jax.default_device(device):
        layers = [{name: leaf(shape, kind) for name, (shape, kind) in _shapes(cfg, mixer, mlp).items()}
                  for mixer, mlp in cfg.layers]
        D = cfg.hidden_size
        return {
            "embed": normal((cfg.vocab_size, D), 1 / math.sqrt(D)),
            "head": normal((D, cfg.vocab_size), 1 / math.sqrt(D)),
            "final_norm": jnp.ones((D,), dtype),
            "layers": layers,
        }


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _draw(key, shape, std, dtype_name):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(jnp.dtype(dtype_name))


# --------------------------------------------------------------------- //
# Caches and the memory plan


def init_paged_cache(cfg: Glm5NextConfig, pool_pages: int, page_size: int, num_slots: int,
                     dtype: jnp.dtype = jnp.bfloat16) -> Caches:
    if page_size % cfg.index_kpool:
        raise ValueError("a page must hold whole index groups")
    H, Dk = cfg.num_heads, cfg.kda_head_dim
    n_kda, n_dsa = len(cfg.layers_of("kda")), len(cfg.layers_of("dsa"))
    gpp = page_size // cfg.index_kpool
    return {
        "lat": [jnp.zeros((pool_pages, page_size, cfg.kv_lora_rank), dtype) for _ in range(n_dsa)],
        "idx": [jnp.zeros((pool_pages, gpp, cfg.index_head_dim), dtype) for _ in range(n_dsa)],
        "idx_sum": [jnp.zeros((num_slots, cfg.index_head_dim), jnp.float32) for _ in range(n_dsa)],
        "kda": [jnp.zeros((num_slots, H, Dk, Dk), jnp.float32) for _ in range(n_kda)],
        "conv": [jnp.zeros((num_slots, cfg.kda_conv - 1, 3 * cfg.kda_dim), dtype) for _ in range(n_kda)],
        "stats": jnp.zeros((len(STAT_NAMES),), jnp.int32),
    }


def kv_bytes_per_token(cfg: Glm5NextConfig, kv_bytes: float = 2) -> int:
    """Paged bytes a cached token costs: a latent row and its share of a
    pooled index key, each sparse-attention layer."""
    per = cfg.kv_lora_rank + cfg.index_head_dim / cfg.index_kpool
    return int(len(cfg.layers_of("dsa")) * per * kv_bytes)


def fixed_state_bytes_per_slot(cfg: Glm5NextConfig, kv_bytes: float = 2) -> int:
    kda = cfg.num_heads * cfg.kda_head_dim ** 2 * 4 + (cfg.kda_conv - 1) * 3 * cfg.kda_dim * kv_bytes
    return int(len(cfg.layers_of("kda")) * kda + len(cfg.layers_of("dsa")) * cfg.index_head_dim * 4)


def serving_memory_bytes(cfg: Glm5NextConfig, batch: int, max_seq_len: int,
                         weight_bytes: int = 2, kv_bytes: float = 2) -> Dict[str, int]:
    weights = count_logical_params(cfg) * weight_bytes
    paged = batch * max_seq_len * kv_bytes_per_token(cfg, kv_bytes)
    fixed = batch * fixed_state_bytes_per_slot(cfg, kv_bytes)
    return {"weights": weights, "kv_cache": paged + fixed, "fixed_state": fixed,
            "total": weights + paged + fixed}


def read_stats(caches: Caches):
    return caches["stats"]


# --------------------------------------------------------------------- //
# Small mathematics


def _mm(x, w):
    """A product in the weights' dtype, float32 out."""
    return jnp.matmul(x.astype(w.dtype), w, preferred_element_type=jnp.float32)


def rms_norm(x, w, eps: float, out_dtype=None):
    x32 = x.astype(jnp.float32)
    y = x32 * lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps) * w.astype(jnp.float32)
    return y.astype(out_dtype or w.dtype)


def _sum4(m, axis: int):
    """Sum of the (few) entries along ``axis`` as explicit adds, kept
    broadcastable: elementwise, so XLA fuses every Sinkhorn sweep into
    one loop where a reduction would end the fusion each time."""
    parts = [lax.slice_in_dim(m, i, i + 1, axis=axis) for i in range(m.shape[axis])]
    out = parts[0]
    for p in parts[1:]:
        out = out + p
    return out


def sinkhorn(m, iters: int, eps: float):
    """Alternating row and column normalisations of a positive [.., n, n]."""
    for _ in range(iters):
        m = m / (_sum4(m, m.ndim - 1) + eps)
        m = m / (_sum4(m, m.ndim - 2) + eps)
    return m


def hc_maps(X, lp: Params, sub: str, cfg: Glm5NextConfig):
    """X [.., n, D] float32 -> (Hpre [.., n], Hpost [.., n], Hres [.., n, n])."""
    n = cfg.hc_mult
    flat = X.reshape(X.shape[:-2] + (n * X.shape[-1],))
    u = rms_norm(flat, lp[f"hc_{sub}_norm"], cfg.norm_eps, jnp.float32)
    z = jnp.matmul(u, lp[f"hc_{sub}_phi"], precision=_HI)
    a, b = lp[f"hc_{sub}_a"], lp[f"hc_{sub}_b"]
    pre = jax.nn.sigmoid(a[0] * z[..., :n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(a[1] * z[..., n:2 * n] + b[n:2 * n])
    res = jnp.exp(a[2] * z[..., 2 * n:] + b[2 * n:]).reshape(z.shape[:-1] + (n, n))
    return pre, post, sinkhorn(res, cfg.hc_sinkhorn_iters, cfg.hc_eps)


def hc_sublayer(X, lp: Params, sub: str, cfg: Glm5NextConfig, fn):
    """One mHC sublayer: ``fn`` maps the normed [.., D] input to [.., D]."""
    with jax.named_scope("mhc_mix"):
        pre, post, res = hc_maps(X, lp, sub, cfg)
        x = _sum4(pre[..., None] * X, X.ndim - 2)[..., 0, :]
        x = rms_norm(x, lp[f"ln_{sub}"], cfg.norm_eps)
    y = fn(x).astype(jnp.float32)
    with jax.named_scope("mhc_mix"):
        n = cfg.hc_mult
        mixed = sum(res[..., :, j:j + 1] * X[..., j:j + 1, :] for j in range(n))
        return mixed + post[..., None] * y[..., None, :]


def swiglu_mlp(x, w_gate_up, w_down, limit: float, oai_alpha: Optional[float] = None):
    gu = _mm(x, w_gate_up)
    F = gu.shape[-1] // 2
    return _mm(expert_ops.swiglu(gu[..., :F], gu[..., F:], limit, oai_alpha), w_down)


def route(x, lp: Params, cfg: Glm5NextConfig):
    """x [N, D] -> (experts [N, k] int32 among ALL routed experts, gates
    [N, k] float32): sigmoid scores, top-k of score + bias, the chosen
    scores normalised and scaled."""
    s = jax.nn.sigmoid(jnp.matmul(x.astype(jnp.float32), lp["router"], precision=_HI))
    _, top = lax.top_k(s + lp["e_bias"], cfg.num_experts_per_tok)
    chosen = jnp.take_along_axis(s, top, axis=-1)
    gates = cfg.routed_scaling_factor * chosen / jnp.sum(chosen, axis=-1, keepdims=True)
    return top.astype(jnp.int32), gates


def moe(x, lp: Params, cfg: Glm5NextConfig, count, kernel: Optional[str], expert_dtype=None,
        oai_alpha: Optional[float] = None):
    """x [N, D] -> (shared expert + held routed experts [N, D] float32,
    stats [6] in ``MOE_STAT_NAMES``' order: pairs held, pairs absent,
    experts hit, experts held, row tiles the grouped product ran, row
    tiles its plan sized for the worst case) over
    the tokens ``count`` [N] bool marks. ``expert_dtype``: what the routed
    experts multiply x in (None: as it comes); the router scores x as it
    comes either way. ``oai_alpha``: the activation of routed and shared
    experts alike (``ops/grouped_matmul.py`` ``swiglu``)."""
    with jax.named_scope("moe_route"):
        top, gates = route(x, lp, cfg)
        E = cfg.experts_held
        local = top - cfg.experts_first
        held = (local >= 0) & (local < E)
        local = jnp.where(held & count[:, None], local, E)  # uncounted tokens route nowhere
    with jax.named_scope("moe_experts"):
        routed, sizes = expert_ops.grouped_mlp(
            x if expert_dtype is None else x.astype(expert_dtype), local, gates,
            lp["we_gate_up"], lp["we_down"], limit=cfg.swiglu_limit, kernel=kernel, oai_alpha=oai_alpha)
        shared = swiglu_mlp(x, lp["ws_gate_up"], lp["ws_down"], cfg.swiglu_limit, oai_alpha)
    n_held = jnp.sum(sizes)
    n_all = jnp.sum(count.astype(jnp.int32)) * cfg.num_experts_per_tok
    tiles_used, tiles_planned = expert_ops.tile_counts(sizes, top.size)
    stats = jnp.stack([n_held, n_all - n_held, jnp.sum((sizes > 0).astype(jnp.int32)), jnp.asarray(E, jnp.int32),
                       tiles_used, jnp.asarray(tiles_planned, jnp.int32)]).astype(jnp.int32)
    return shared + routed, stats


def mlp_sublayer(X, lp: Params, mlp: str, cfg: Glm5NextConfig, count, kernel: Optional[str]):
    """The MLP sublayer over X [.., n, D]; returns (X, moe stats or None)."""
    box = []

    def fn(x):
        if mlp == "dense":
            return swiglu_mlp(x, lp["w_gate_up"], lp["w_down"], cfg.swiglu_limit)
        y, stats = moe(x.reshape(-1, x.shape[-1]), lp, cfg, count.reshape(-1), kernel)
        box.append(stats)
        return y.reshape(x.shape[:-1] + (y.shape[-1],))

    X = hc_sublayer(X, lp, "mlp", cfg, fn)
    return X, (box[0] if box else None)


def head(params: Params, cfg: Glm5NextConfig, hidden):
    """hidden [N, D] (the SUM of the streams) -> float32 logits [N, V]."""
    return _mm(rms_norm(hidden, params["final_norm"], cfg.norm_eps), params["head"])


def _embed_streams(params: Params, cfg: Glm5NextConfig, tokens):
    e = params["embed"][tokens].astype(jnp.float32)
    return jnp.broadcast_to(e[..., None, :], e.shape[:-1] + (cfg.hc_mult, e.shape[-1]))


# --------------------------------------------------------------------- //
# KDA


def _kda_inputs(x, conv_cat, lp: Params, cfg, beta_scale: float = 1.0, lower_bound: Optional[float] = None):
    """From the normed input x [.., T, D] and the convolution's input
    ``conv_cat`` [.., T + conv - 1, 3K] (the tail, then this call's
    projections): q, k, v [.., T, H, Dk], beta [.., T, H], g [.., T, H, Dk]
    (log decay, <= 0), the output gate [.., T, K]. float32. ``beta`` is
    ``beta_scale * sigmoid`` (2: the transition's eigenvalue along k may
    be negative); ``lower_bound`` clamps the log decay (None: no clamp).
    ``cfg`` is any configuration with the KDA sizes (``models/solaropen2.py``
    calls this too)."""
    H, Dk, r = cfg.num_heads, cfg.kda_head_dim, cfg.kda_rank
    T = x.shape[-2]
    w = lp["conv_w"]
    qkv = sum(lax.slice_in_dim(conv_cat, i, i + T, axis=conv_cat.ndim - 2) * w[i] for i in range(cfg.kda_conv))
    qkv = jax.nn.silu(qkv)
    q, k, v = (t.reshape(t.shape[:-1] + (H, Dk)) for t in jnp.split(qkv, 3, axis=-1))
    q = q * lax.rsqrt(jnp.sum(q * q, axis=-1, keepdims=True) + 1e-6) * (Dk ** -0.5)
    k = k * lax.rsqrt(jnp.sum(k * k, axis=-1, keepdims=True) + 1e-6)
    small = _mm(x, lp["wbfg"])
    beta = jax.nn.sigmoid(small[..., :H])
    if beta_scale != 1.0:
        beta = beta_scale * beta
    f = _mm(small[..., H:H + r], lp["wf2"]) + lp["dt_bias"]
    g = -jnp.exp(lp["A_log"])[:, None] * jax.nn.softplus(f).reshape(f.shape[:-1] + (H, Dk))
    if lower_bound is not None:
        g = jnp.maximum(g, lower_bound)
    gate = jax.nn.sigmoid(_mm(small[..., H + r:], lp["wg2"]))
    return q, k, v, beta, g, gate


def _kda_output(o, gate, lp: Params, cfg: Glm5NextConfig):
    o = rms_norm(o, lp["o_norm"], cfg.norm_eps, jnp.float32)
    return _mm(o.reshape(o.shape[:-2] + (cfg.kda_dim,)) * gate, lp["wo"])


def kda_step(S, q, k, v, beta, g):
    """One token of the delta rule with per-channel decay. S [.., Dk, Dv];
    q, k, g [.., Dk]; v [.., Dv]; beta [..]. Returns (o [.., Dv], S).
    ``S <- Diag(exp g) S; S <- S + beta k (v - S^T k)^T; o = S^T q``,
    arranged so that the old state is read once for both products and
    written once."""
    a = jnp.exp(g)
    both = jnp.stack([a * k, a * q], axis=-2)  # [.., 2, Dk]
    red = jnp.sum(S[..., None, :, :] * both[..., None], axis=-2)  # [.., 2, Dv]
    u = beta[..., None] * (v - red[..., 0, :])
    o = red[..., 1, :] + jnp.sum(k * q, axis=-1, keepdims=True) * u
    return o, a[..., None] * S + k[..., None] * u[..., None, :]


def kda_chunk(S, q, k, v, beta, g, block: int = KDA_BLOCK):
    """The same recurrence over T tokens, block-wise (WY / UT transform).
    S [N, H, Dk, Dv]; q, k, g [N, T, H, Dk]; v [N, T, H, Dv]; beta
    [N, T, H]. A token with beta = 0 and g = 0 leaves the state as it is.
    Returns (o [N, T, H, Dv], S).

    Inside a block, with ``G`` the cumulative log decay from the block's
    start, ``kp = k e^G``, ``km = k e^-G``, ``qp = q e^G``:
    ``A = strict_tril(beta kp km^T)``, ``T = (I + A)^-1``,
    ``U = T beta v - T beta kp S``, ``O = qp S + tril(qp km^T) U``,
    ``S <- e^G_end (S + km^T U)``."""
    N, T, H, Dk = q.shape
    B = min(block, T)
    nb = T // B
    assert nb * B == T, (T, B)

    def blocks(x):  # [N, T, H, D] -> [nb, N, H, B, D]
        return jnp.transpose(x.reshape(N, nb, B, H, x.shape[-1]), (1, 0, 3, 2, 4))

    qb, kb, vb, gb = blocks(q), blocks(k), blocks(v), blocks(g)
    bb = jnp.transpose(beta.reshape(N, nb, B, H), (1, 0, 3, 2))  # [nb, N, H, B]
    G = jnp.cumsum(gb, axis=-2)
    eG = jnp.exp(G)
    kp, km, qp = kb * eG, kb * jnp.exp(-G), qb * eG
    lower = jnp.tril(jnp.ones((B, B), bool), -1)
    A = jnp.where(lower, jnp.einsum("...tk,...ik->...ti", kp, km, precision=_HI) * bb[..., None], 0.0)
    # (I + A)^-1 = (I - A)(I + A^2)(I + A^4)...: A is strictly lower, so A^B = 0
    eye = jnp.eye(B, dtype=jnp.float32)
    Tm, P = eye - A, jnp.matmul(A, A, precision=_HI)
    for _ in range(max(0, (B - 1).bit_length() - 1)):
        Tm = jnp.matmul(Tm, eye + P, precision=_HI)
        P = jnp.matmul(P, P, precision=_HI)
    Wv = jnp.matmul(Tm, bb[..., None] * vb, precision=_HI)
    Wk = jnp.matmul(Tm, bb[..., None] * kp, precision=_HI)
    Pq = jnp.where(jnp.tril(jnp.ones((B, B), bool)), jnp.einsum("...tk,...ik->...ti", qp, km, precision=_HI), 0.0)
    g_end = eG[..., -1, :]  # [nb, N, H, Dk]

    def body(S, xs):
        Wv_b, Wk_b, qp_b, Pq_b, km_b, ge_b = xs
        U = Wv_b - jnp.matmul(Wk_b, S, precision=_HI)
        O = jnp.matmul(qp_b, S, precision=_HI) + jnp.matmul(Pq_b, U, precision=_HI)
        S = ge_b[..., None] * (S + jnp.einsum("...tk,...tv->...kv", km_b, U, precision=_HI))
        return S, O

    S, O = lax.scan(body, S, (Wv, Wk, qp, Pq, km, g_end))
    O = jnp.transpose(O, (1, 0, 3, 2, 4))  # [nb, N, H, B, Dv] -> [N, nb, B, H, Dv]
    return O.reshape(N, T, H, O.shape[-1]), S


# --------------------------------------------------------------------- //
# The indexer and the latent read


def _rope(x, positions, cfg: Glm5NextConfig):
    """Interleaved RoPE over the first ``index_rope_dim`` of the last
    axis. x [.., T, (h,) Di] float32, positions [.., T]."""
    R = cfg.index_rope_dim
    inv = cfg.rope_theta ** (-jnp.arange(0, R, 2, dtype=jnp.float32) / R)
    ang = positions.astype(jnp.float32)[..., None] * inv  # [.., T, R/2]
    if x.ndim == ang.ndim + 1:
        ang = ang[..., None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    rot = x[..., :R].reshape(x.shape[:-1] + (R // 2, 2))
    a, b = rot[..., 0], rot[..., 1]
    out = jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1).reshape(x.shape[:-1] + (R,))
    return jnp.concatenate([out, x[..., R:]], axis=-1)


def _layer_norm(x, w, b, eps: float):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * lax.rsqrt(var + eps) * w.astype(jnp.float32) + b.astype(jnp.float32)


def _dsa_project(x, positions, lp: Params, cfg: Glm5NextConfig):
    """x [.., T, D] normed -> absorbed queries qlat [.., T, H, R], the
    per-head queries q [.., T, H, Dq] they absorb (float32), index queries qi
    [.., T, Hi, Di], head weights w [.., T, Hi], the latent c [.., T, R]
    (cache dtype), the index key ki [.., T, Di] float32."""
    H, Dq, R = cfg.num_heads, cfg.qk_head_dim, cfg.kv_lora_rank
    Hi, Di, ql = cfg.index_n_heads, cfg.index_head_dim, cfg.q_lora_rank
    xp = _mm(x, lp["wx"])
    cq = rms_norm(xp[..., :ql], lp["q_norm"], cfg.norm_eps)
    c = rms_norm(xp[..., ql:ql + R], lp["kv_norm"], cfg.norm_eps)
    ki = _rope(_layer_norm(xp[..., ql + R:ql + R + Di], lp["ki_norm_w"], lp["ki_norm_b"], cfg.norm_eps),
               positions, cfg)
    w = xp[..., ql + R + Di:] * (Hi ** -0.5 * Di ** -0.5)
    cqp = _mm(cq, lp["wcq"])
    q = cqp[..., :H * Dq].reshape(cqp.shape[:-1] + (H, Dq))
    qi = _rope(cqp[..., H * Dq:].reshape(cqp.shape[:-1] + (Hi, Di)), positions, cfg)
    qlat = jnp.einsum("...hd,hdr->...hr", q.astype(lp["wuk"].dtype), lp["wuk"], preferred_element_type=jnp.float32)
    return qlat, q, qi, w, c, ki


def index_scores(qi, w, keys):
    """qi [.., T, Hi, Di], w [.., T, Hi], keys [.., G, Di] (the pooled
    keys, cache dtype) -> [.., T, G] float32: sum_j w_j relu(qi_j . K_g)."""
    dots = jnp.einsum("...thd,...gd->...thg", qi.astype(keys.dtype), keys, preferred_element_type=jnp.float32)
    return jnp.sum(jax.nn.relu(dots) * w[..., None], axis=-2)


def select_groups(scores, n_complete, topk: int):
    """scores [.., T, G]; ``n_complete`` [.., T]: groups 0..n-1 may be
    chosen. The ``topk`` best of them (all, if fewer), ties to the lower
    index: [.., T, G] bool. Ranked by counting, not sorting: one fused
    compare-and-sum."""
    G = scores.shape[-1]
    gi = jnp.arange(G, dtype=jnp.int32)
    ok = gi < n_complete[..., None]
    s = jnp.where(ok, scores, -jnp.inf)
    ahead = (s[..., None, :] > s[..., :, None]) | ((s[..., None, :] == s[..., :, None]) & (gi[None, :] < gi[:, None]))
    rank = jnp.sum(ahead.astype(jnp.int32), axis=-1)
    return ok & (rank < topk)


def token_mask(sel, positions, cfg: Glm5NextConfig):
    """Group selection [.., T, G] and the queries' positions [.., T] ->
    the tokens each query reads [.., T, G * kpool] bool: its selected
    groups, and its own open group up to itself."""
    kp = cfg.index_kpool
    S = sel.shape[-1] * kp
    tok = jnp.arange(S, dtype=jnp.int32)
    pos = positions[..., None]
    tail = (tok // kp == pos // kp) & (tok <= pos)
    return jnp.repeat(sel, kp, axis=-1) | tail


def _dsa_output(acc, lp: Params, cfg: Glm5NextConfig):
    """acc [.., H, R] (sum_s p_s c_s) -> the mixer's output [.., D]."""
    o = jnp.einsum("...hr,hrv->...hv", acc.astype(lp["wuv"].dtype), lp["wuv"], preferred_element_type=jnp.float32)
    return _mm(o.reshape(o.shape[:-2] + (cfg.num_heads * cfg.v_head_dim,)), lp["wo"])


def _write_rows(pool, page, row, values):
    """pool [P, rows, W] <- values [.., W] at (page, row) [..]; a page of
    P or more is dropped."""
    return pool.at[page, row].set(values.astype(pool.dtype), mode="drop")


def _attend_blocks(qlat, lat_pool, pages, mask, n_tokens, scale: float, block_pages: int = 4):
    """Chunk attention over a row's pages with a running softmax, in
    blocks of ``block_pages`` pages, as far as ``n_tokens`` [N] reach: ONE
    program whatever the context. qlat [N, T, H, R]; pages [N, Pmax];
    mask [N, T, Pmax * page]. Returns sum_s p_s c_s [N, T, H, R]."""
    N, T, H, R = qlat.shape
    page = lat_pool.shape[1]
    Pmax = pages.shape[1]
    bp = min(block_pages, Pmax)
    while Pmax % bp:
        bp -= 1
    W = bp * page
    n_blocks = jnp.max((n_tokens + W - 1) // W)
    q = jnp.moveaxis(qlat, 2, 1).astype(lat_pool.dtype)  # [N, H, T, R]

    def body(i, carry):
        m, l, acc = carry
        pg = lax.dynamic_slice_in_dim(pages, i * bp, bp, axis=1)
        c = lat_pool[pg].reshape(N, W, R)
        ok = lax.dynamic_slice_in_dim(mask, i * W, W, axis=2)[:, None]  # [N, 1, T, W]
        sc = jnp.einsum("nhtr,nsr->nhts", q, c, preferred_element_type=jnp.float32) * scale
        sc = jnp.where(ok, sc, _NEG)
        m_new = jnp.maximum(m, jnp.max(sc, axis=-1, keepdims=True))
        p = jnp.where(ok, jnp.exp(sc - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        l = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        acc = alpha * acc + jnp.einsum("nhts,nsr->nhtr", p.astype(c.dtype), c, preferred_element_type=jnp.float32)
        return m_new, l, acc

    init = (jnp.full((N, H, T, 1), _NEG, jnp.float32), jnp.zeros((N, H, T, 1), jnp.float32),
            jnp.zeros((N, H, T, R), jnp.float32))
    _, l, acc = lax.fori_loop(0, n_blocks, body, init)
    return jnp.moveaxis(acc / jnp.where(l == 0.0, 1.0, l), 1, 2)


# --------------------------------------------------------------------- //
# The chunk walk: prefill and chunked extend


def _chunk_walk(params: Params, cfg: Glm5NextConfig, caches: Caches, tokens, offsets, valid, slots,
                tables, page_size: int, grouped_matmul: Optional[str] = None,
                capture: Optional[Dict[str, Any]] = None):
    """All layers over a chunk [N, C] per row; returns (the summed
    streams of each row's last valid position [N, D], caches).

    A row at ``offsets == 0`` starts from a zero state (which is what
    resets a slot at admission); a row at ``offsets > 0`` carries its
    slot's state on. A row with ``valid == 0`` changes nothing: its pool
    writes are dropped and its slot's state is written back as it was.
    The latent read walks each row's pages as far as its context reaches
    whatever window the engine names: one program a chunk width.
    ``capture`` (a dict) receives ``selection``: the groups [N, C, G] the
    first sparse-attention layer chose for each query (the benchmark
    holds them against its reference's)."""
    N, C = tokens.shape
    S = tables.shape[1] * page_size
    kp = cfg.index_kpool
    idx = jnp.arange(C, dtype=jnp.int32)
    positions = jnp.minimum(offsets[:, None] + idx[None, :], S - 1)
    tok_valid = idx[None, :] < valid[:, None]
    row_live = valid > 0
    started = row_live & (offsets > 0)
    last = jnp.clip(valid, 1, C) - 1
    row_tables = tables[slots]
    P = caches["lat"][0].shape[0] if caches["lat"] else 0

    X = _embed_streams(params, cfg, tokens)  # [N, C, n, D]
    new = {k: list(v) if isinstance(v, list) else v for k, v in caches.items()}
    moe_stats = jnp.zeros((len(MOE_STAT_NAMES),), jnp.int32)
    dsa_stats = jnp.zeros((2,), jnp.int32)
    i_kda = i_dsa = 0
    for l, (mixer, mlp) in enumerate(cfg.layers):
        lp = params["layers"][l]
        if mixer == "kda":
            i = i_kda
            i_kda += 1

            def mix(x, lp=lp, i=i):
                with jax.named_scope("kda_chunk"):
                    old_S, old_tail = caches["kda"][i][slots], caches["conv"][i][slots]
                    proj = _mm(x, lp["wqkv"])
                    tail = jnp.where(started[:, None, None], old_tail.astype(jnp.float32), 0.0)
                    cat = jnp.concatenate([tail, proj], axis=1)
                    q, k, v, beta, g, gate = _kda_inputs(x, cat, lp, cfg, lower_bound=cfg.gate_lower_bound)
                    beta = jnp.where(tok_valid[..., None], beta, 0.0)
                    g = jnp.where(tok_valid[..., None, None], g, 0.0)
                    S0 = jnp.where(started[:, None, None, None], old_S, 0.0).astype(jnp.float32)
                    o, S1 = kda_chunk(S0, q, k, v, beta, g)
                    taps = valid[:, None] + jnp.arange(cfg.kda_conv - 1, dtype=jnp.int32)[None, :]
                    new_tail = jnp.take_along_axis(cat, taps[:, :, None], axis=1).astype(old_tail.dtype)
                    keep = row_live[:, None, None]
                    new["kda"][i] = caches["kda"][i].at[slots].set(
                        jnp.where(keep[..., None], S1.astype(old_S.dtype), old_S))
                    new["conv"][i] = caches["conv"][i].at[slots].set(jnp.where(keep, new_tail, old_tail))
                    return _kda_output(o, gate, lp, cfg)
        else:
            i = i_dsa
            i_dsa += 1

            def mix(x, lp=lp, i=i):
                nonlocal dsa_stats
                with jax.named_scope("dsa_index"):
                    qlat, _, qi, w, c, ki = _dsa_project(x, positions, lp, cfg)
                    phys = jnp.take_along_axis(row_tables, positions // page_size, axis=1)
                    phys = jnp.where(tok_valid, phys, P)  # padding: dropped
                    lat = _write_rows(caches["lat"][i], phys, positions % page_size, c)
                    # pooled keys of the chunk's complete groups; the open one's sum stays with the slot
                    kg = ki.reshape(N, C // kp, kp, ki.shape[-1])
                    g_first = offsets // kp
                    g_ids = g_first[:, None] + jnp.arange(C // kp, dtype=jnp.int32)[None, :]
                    complete = (jnp.arange(C // kp, dtype=jnp.int32)[None, :] + 1) * kp <= valid[:, None]
                    gpp = page_size // kp
                    g_phys = jnp.take_along_axis(row_tables, jnp.minimum(g_ids // gpp, tables.shape[1] - 1), axis=1)
                    g_phys = jnp.where(complete, g_phys, P)
                    ipool = _write_rows(caches["idx"][i], g_phys, g_ids % gpp, jnp.mean(kg, axis=2))
                    in_open = tok_valid & (idx[None, :] >= (valid[:, None] // kp) * kp)
                    open_sum = jnp.sum(jnp.where(in_open[..., None], ki, 0.0), axis=1)
                    old_sum = caches["idx_sum"][i][slots]
                    new["idx_sum"][i] = caches["idx_sum"][i].at[slots].set(
                        jnp.where(row_live[:, None], open_sum, old_sum))
                    new["lat"][i], new["idx"][i] = lat, ipool
                    keys = ipool[row_tables].reshape(N, S // kp, ki.shape[-1])
                    sel = select_groups(index_scores(qi, w, keys), positions // kp, cfg.topk_groups)
                    if capture is not None:
                        capture.setdefault("selection", sel)
                    mask = token_mask(sel, positions, cfg) & tok_valid[..., None]
                    dsa_stats = dsa_stats + jnp.stack([
                        jnp.sum(mask.astype(jnp.int32)), jnp.sum(jnp.where(tok_valid, positions + 1, 0))])
                with jax.named_scope("dsa_attn"):
                    acc = _attend_blocks(qlat, lat, row_tables, mask, jnp.where(row_live, offsets + valid, 0),
                                         cfg.qk_head_dim ** -0.5)
                    return _dsa_output(acc, lp, cfg)

        X = hc_sublayer(X, lp, "mix", cfg, mix)
        X, stats = mlp_sublayer(X, lp, mlp, cfg, tok_valid, grouped_matmul)
        if stats is not None:
            moe_stats = moe_stats + stats
    # the block-wise recurrence advanced every state: the step kernel none
    new["stats"] = jnp.concatenate([moe_stats, dsa_stats, jnp.zeros((1,), jnp.int32)]).astype(jnp.int32)
    h_last = jnp.take_along_axis(jnp.sum(X, axis=2), last[:, None, None], axis=1)[:, 0]
    return h_last, new


def prefill_paged(params: Params, cfg: Glm5NextConfig, caches: Caches, tokens, lengths, slots, tables,
                  page_size: int, grouped_matmul: Optional[str] = None, delta_step: Optional[str] = None,
                  **_paths):
    """A monolithic admission wave: (last-position logits [N, V], caches)."""
    del delta_step  # the step kernel serves decode; a chunk walks block-wise
    hidden, caches = _chunk_walk(params, cfg, caches, tokens, jnp.zeros_like(lengths), lengths, slots,
                                 tables, page_size, grouped_matmul)
    return head(params, cfg, hidden), caches


def extend_paged(params: Params, cfg: Glm5NextConfig, caches: Caches, tokens, offsets, valid, slots,
                 tables, window: int, page_size: int, grouped_matmul: Optional[str] = None,
                 delta_step: Optional[str] = None, capture: Optional[Dict[str, Any]] = None, **_paths):
    """One chunk of a chunked prefill: (summed streams [N, D] of each
    row's last valid position, caches)."""
    del window, delta_step  # the latent read follows each row's own context; a chunk walks block-wise
    return _chunk_walk(params, cfg, caches, tokens, offsets, valid, slots, tables, page_size,
                       grouped_matmul, capture)


# --------------------------------------------------------------------- //
# One decode step


def decode_paged(params: Params, cfg: Glm5NextConfig, caches: Caches, tokens, positions, live, tables,
                 window: Optional[int], page_size: int, page_kernel: Optional[str] = None,
                 grouped_matmul: Optional[str] = None, delta_step: Optional[str] = None, **_paths):
    """One token per slot: (logits [B, V], caches). A dead row leaves
    every fixed state as it is and writes nothing to the pools.
    ``delta_step`` ('compiled' / 'interpret') advances KDA's state with
    ``ops/delta_rule.py``, in place; None with ``kda_step``."""
    del window
    B = tokens.shape[0]
    S = tables.shape[1] * page_size
    kp = cfg.index_kpool
    P = caches["lat"][0].shape[0] if caches["lat"] else 0
    phys = jnp.where(live, jnp.take_along_axis(tables, (positions // page_size)[:, None], axis=1)[:, 0], P)
    # one work list a step, the pages a grid step that the kernel's rule names
    work = (latent_attention.decode_work_list(caches["lat"][0], tables, positions)
            if page_kernel and caches["lat"] else None)

    X = _embed_streams(params, cfg, tokens)  # [B, n, D]
    new = {k: list(v) if isinstance(v, list) else v for k, v in caches.items()}
    moe_stats = jnp.zeros((len(MOE_STAT_NAMES),), jnp.int32)
    dsa_stats = jnp.zeros((2,), jnp.int32)
    i_kda = i_dsa = 0
    for l, (mixer, mlp) in enumerate(cfg.layers):
        lp = params["layers"][l]
        if mixer == "kda":
            i = i_kda
            i_kda += 1

            def mix(x, lp=lp, i=i):
                with jax.named_scope("kda_step"):
                    old_S, old_tail = caches["kda"][i], caches["conv"][i]
                    proj = _mm(x, lp["wqkv"])
                    cat = jnp.concatenate([old_tail.astype(jnp.float32), proj[:, None]], axis=1)
                    q, k, v, beta, g, gate = _kda_inputs(x[:, None], cat, lp, cfg, lower_bound=cfg.gate_lower_bound)
                    step = (q[:, 0], k[:, 0], v[:, 0], beta[:, 0], g[:, 0])
                    keep = live[:, None, None]
                    if delta_step:
                        o, new["kda"][i] = delta_rule.delta_rule_step(
                            old_S, *step, live, interpret=(delta_step == "interpret"))
                    else:
                        o, S1 = kda_step(old_S.astype(jnp.float32), *step)
                        new["kda"][i] = jnp.where(keep[..., None], S1.astype(old_S.dtype), old_S)
                    new["conv"][i] = jnp.where(keep, cat[:, 1:].astype(old_tail.dtype), old_tail)
                    return _kda_output(o, gate[:, 0], lp, cfg)
        else:
            i = i_dsa
            i_dsa += 1

            def mix(x, lp=lp, i=i):
                nonlocal dsa_stats
                with jax.named_scope("dsa_index"):
                    qlat, _, qi, w, c, ki = _dsa_project(x[:, None], positions[:, None], lp, cfg)
                    lat = _write_rows(caches["lat"][i], phys, positions % page_size, c[:, 0])
                    old_sum = caches["idx_sum"][i]
                    run = jnp.where((positions % kp == 0)[:, None], 0.0, old_sum) + ki[:, 0]
                    new["idx_sum"][i] = jnp.where(live[:, None], run, old_sum)
                    closes = live & (positions % kp == kp - 1)
                    ipool = _write_rows(caches["idx"][i], jnp.where(closes, phys, P),
                                        (positions % page_size) // kp, run / kp)
                    new["lat"][i], new["idx"][i] = lat, ipool
                    keys = ipool[tables].reshape(B, S // kp, ki.shape[-1])
                    sel = select_groups(index_scores(qi, w, keys), (positions // kp)[:, None], cfg.topk_groups)
                    mask = token_mask(sel, positions[:, None], cfg)[:, 0]  # [B, S]
                    dsa_stats = dsa_stats + jnp.stack([
                        jnp.sum((mask & live[:, None]).astype(jnp.int32)), jnp.sum(jnp.where(live, positions + 1, 0))])
                with jax.named_scope("dsa_attn"):
                    scale = cfg.qk_head_dim ** -0.5
                    if page_kernel:
                        acc = latent_attention.latent_attention(
                            qlat[:, 0].astype(lat.dtype), lat, jnp.where(mask, 0.0, _NEG), tables, positions,
                            scale=scale, interpret=(page_kernel == "interpret"), work=work)
                    else:
                        cs = lat[tables].reshape(B, S, lat.shape[-1])
                        sc = jnp.einsum("bhr,bsr->bhs", qlat[:, 0].astype(cs.dtype), cs,
                                        preferred_element_type=jnp.float32) * scale
                        p = jax.nn.softmax(jnp.where(mask[:, None], sc, _NEG), axis=-1)
                        acc = jnp.einsum("bhs,bsr->bhr", p.astype(cs.dtype), cs, preferred_element_type=jnp.float32)
                    return _dsa_output(acc, lp, cfg)

        X = hc_sublayer(X, lp, "mix", cfg, mix)
        X, stats = mlp_sublayer(X, lp, mlp, cfg, live, grouped_matmul)
        if stats is not None:
            moe_stats = moe_stats + stats
    kernel_rows = jnp.sum(live.astype(jnp.int32)) if delta_step and i_kda else jnp.zeros((), jnp.int32)
    new["stats"] = jnp.concatenate([moe_stats, dsa_stats, kernel_rows[None]]).astype(jnp.int32)
    return head(params, cfg, jnp.sum(X, axis=1)), new


# --------------------------------------------------------------------- //
# The whole sequence at once, token by token, no cache: what the tests
# hold the paged walks against (the plain reference of the benchmark is
# perfbench/arch/glm5next.py and imports nothing from here)


def forward_full(params: Params, cfg: Glm5NextConfig, tokens):
    """Logits [N, T, V] of tokens [N, T]: KDA as a token-by-token scan of
    ``kda_step``, the latent attention unabsorbed and dense with the
    selection mask, the experts densely over the held ones."""
    N, T = tokens.shape
    kp = cfg.index_kpool
    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None, :], (N, T))
    X = _embed_streams(params, cfg, tokens)
    everyone = jnp.ones((N, T), bool)
    for l, (mixer, mlp) in enumerate(cfg.layers):
        lp = params["layers"][l]
        if mixer == "kda":
            def mix(x, lp=lp):
                proj = _mm(x, lp["wqkv"])
                cat = jnp.pad(proj, ((0, 0), (cfg.kda_conv - 1, 0), (0, 0)))
                q, k, v, beta, g, gate = _kda_inputs(x, cat, lp, cfg, lower_bound=cfg.gate_lower_bound)

                def step(S, xs):
                    o, S = kda_step(S, *xs)
                    return S, o

                S0 = jnp.zeros((N, cfg.num_heads, cfg.kda_head_dim, cfg.kda_head_dim), jnp.float32)
                _, o = lax.scan(step, S0, tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, beta, g)))
                return _kda_output(jnp.moveaxis(o, 0, 1), gate, lp, cfg)
        else:
            def mix(x, lp=lp):
                H, Dq = cfg.num_heads, cfg.qk_head_dim
                _, q, qi, w, c, ki = _dsa_project(x, pos, lp, cfg)
                G = T // kp
                keys = jnp.mean(ki[:, :G * kp].reshape(N, G, kp, -1), axis=2).astype(c.dtype)
                sel = select_groups(index_scores(qi, w, keys), pos // kp, cfg.topk_groups)
                mask = jnp.pad(token_mask(sel, pos, cfg), ((0, 0), (0, 0), (0, T - G * kp)))
                tok = jnp.arange(T, dtype=jnp.int32)
                mask = mask | ((tok[None, None, :] // kp == pos[..., None] // kp) & (tok[None, None, :] <= pos[..., None]))
                kh = jnp.einsum("nsr,hdr->nshd", c, lp["wuk"], preferred_element_type=jnp.float32)
                vh = jnp.einsum("nsr,hrv->nshv", c, lp["wuv"], preferred_element_type=jnp.float32)
                sc = jnp.einsum("nthd,nshd->nhts", q, kh, precision=_HI) * Dq ** -0.5
                p = jax.nn.softmax(jnp.where(mask[:, None], sc, _NEG), axis=-1)
                o = jnp.einsum("nhts,nshv->nthv", p, vh, precision=_HI)
                return _mm(o.reshape(N, T, H * cfg.v_head_dim), lp["wo"])

        X = hc_sublayer(X, lp, "mix", cfg, mix)
        X, _ = mlp_sublayer(X, lp, mlp, cfg, everyone, None)
    return head(params, cfg, jnp.sum(X, axis=2))
