"""GigaChat3.5-432B-A28B (``gigachat3_5``) for the serving engine, as the
share ONE chip holds of an expert-parallel deployment.

Four mechanisms, each with its equations in ISSUE 38 / the benchmark's
reference (``perfbench/arch/gigachat35.py``):

- **Sandwich gated norms.** One residual stream ``x``; every sublayer
  ``F`` (mixer, MLP) is normed before AND after:
  ``x <- x + N_post(F(N_pre(x)))`` with the zero-centred gated norm
  ``N(u) = u / sqrt(mean(u^2) + eps) * (1 + w) * 2 sigmoid(g)``, ``w``
  and ``g`` learned vectors; a final ``N`` before the head. float32.
- **Gated DeltaNet**: 32 key heads feed 64 value heads (value head ``j``
  reads key head ``j // 2``), a ``[128, 128]`` float32 state a value
  head, ONE scalar decay a head and token. Decode is one delta-rule step
  (``ops/delta_rule.py`` where the ``delta_step`` path resolved: the
  state read once and written once, in place, the key heads mapped to
  their value heads inside the kernel; ``gdn_step`` elsewhere); prefill
  and extend compute the same recurrence
  block-wise (``gdn_chunk``). A scalar decay lets the block form use the
  pairwise decays ``exp(G_i - G_j) <= 1`` directly, so nothing is
  divided by a cumulative decay: no lower bound on the gate is needed
  (the model has none) and a block is 64 tokens, where the per-channel
  form of ``models/glm5next.py`` (``kda_chunk``) must stop at 16.
- **Dense latent attention** (MLA with a decoupled RoPE key, absorbed at
  decode): the cache holds ONE row a token, ``[c | k_rope | padding]``
  (512 + 64 padded to 640 columns: whole lane tiles), the key of all 64
  heads; the value is the row's first 512 columns. Every cached token is
  read (``ops/latent_attention.py`` ``dense_latent_attention``); the
  output is gated per channel by ``sigmoid(W_g x)``. The chunk walk reads
  the pool EXPANDED: per-head keys and values rebuilt from the latent a
  block of pages at a time (about half the absorbed form's operations
  at 512 query rows).
- **Experts**: ``models/glm5next.py``'s router and expert layer (a
  sigmoid router over all 256 experts, top 8 of score + bias, of which
  this chip HOLDS ``experts_held``; ``ops/grouped_matmul.py``), shared
  code on purpose: a change there shows in two cells.

**Two kinds of cache** (docs/model_registry.md). Paged: the latent rows
``lat [P, page, 640]`` of each latent-attention layer. Fixed per slot:
the delta-rule state ``[slots, 64, 128, 128]`` float32 and the
convolution's tail ``[slots, 3, 16384]`` of each Gated DeltaNet layer.
``stats`` is a handful of int32 counts of the last walk.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from generativeaiexamples_tpu.models.glm5next import MOE_STAT_NAMES, _mm, _write_rows, moe, rms_norm, swiglu_mlp
from generativeaiexamples_tpu.ops import delta_rule, latent_attention

Params = Dict[str, Any]
Caches = Dict[str, Any]
_HI = lax.Precision.HIGHEST
_NEG = -1e30
_LANE = 128
GDN_BLOCK = 64

STAT_NAMES = MOE_STAT_NAMES + ("latent_tokens_read", "latent_chunk_kernel_layers", "latent_chunk_xla_layers",
                               "state_kernel_rows")


@dataclasses.dataclass(frozen=True)
class GigaChat35Config:
    """Published widths; ``layers`` lists (mixer, mlp) of the layers
    served; ``vocab_size``, ``experts_first`` and ``experts_held`` are
    this chip's share."""

    vocab_size: int = 128256
    hidden_size: int = 7168
    layers: Tuple[Tuple[str, str], ...] = (("gdn", "dense"),) * 3 + (
        ("mla", "sparse"), ("gdn", "sparse"), ("gdn", "sparse"), ("gdn", "sparse"))
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    n_routed_experts: int = 256
    num_experts_per_tok: int = 8
    experts_first: int = 0
    experts_held: int = 256
    routed_scaling_factor: float = 2.5
    swiglu_limit: float = 10.0
    num_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 100000.0
    rope_factor: float = 8.0
    rope_original_max: int = 32768
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    linear_key_heads: int = 32
    linear_value_heads: int = 64
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv: int = 4
    norm_gate_scale: float = 2.0
    linear_gate_scale: float = 2.0
    norm_eps: float = 1e-6
    o_norm_eps: float = 1e-6
    max_seq_len: int = 262144

    @property
    def num_layers(self) -> int:
        return len(self.layers)

    @property
    def key_dim(self) -> int:
        return self.linear_key_heads * self.linear_key_head_dim

    @property
    def value_dim(self) -> int:
        return self.linear_value_heads * self.linear_value_head_dim

    @property
    def conv_dim(self) -> int:
        return 2 * self.key_dim + self.value_dim

    @property
    def latent_row(self) -> int:
        """Columns of a cached row: latent and RoPE key, padded to whole lane tiles."""
        return -(-(self.kv_lora_rank + self.qk_rope_head_dim) // _LANE) * _LANE

    @property
    def softmax_scale(self) -> float:
        """``use_mla_scaling_factor``: DeepSeek-V3's YaRN rule."""
        m = yarn_mscale(self.rope_factor, self.rope_mscale_all_dim)
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5 * m * m

    def layers_of(self, mixer: str):
        return [l for l, (m, _) in enumerate(self.layers) if m == mixer]


_PERIOD = (("gdn", "dense"), ("mla", "sparse"), ("gdn", "sparse"), ("gdn", "sparse"), ("gdn", "sparse"))

PRESETS: Dict[str, GigaChat35Config] = {
    # one chip's share of the 16-way expert-parallel deployment: published
    # layer 0 and the period 3-6, 16 of 256 experts, an eighth of the vocabulary
    "gigachat3.5-432b-a28b-ep16": GigaChat35Config(
        vocab_size=16032, layers=_PERIOD, experts_held=16, max_seq_len=8192),
    # CPU tests: the same five layers at a size a test checks by hand;
    # 2 key heads feed 4 value heads, 2 of 16 experts held
    "gigachat35-debug": GigaChat35Config(
        vocab_size=256, hidden_size=64, layers=_PERIOD, intermediate_size=96,
        moe_intermediate_size=32, n_routed_experts=16, num_experts_per_tok=4, experts_held=2,
        num_heads=4, q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, rope_original_max=64, linear_key_heads=2, linear_value_heads=4,
        linear_key_head_dim=16, linear_value_head_dim=16, max_seq_len=1024,
    ),
}


def validate(cfg: GigaChat35Config) -> None:
    for mixer, mlp in cfg.layers:
        if mixer not in ("gdn", "mla") or mlp not in ("dense", "sparse"):
            raise ValueError(f"unknown layer kinds {(mixer, mlp)}")
    if cfg.experts_first < 0 or cfg.experts_first + cfg.experts_held > cfg.n_routed_experts:
        raise ValueError("the experts held must lie inside the routed experts")
    if cfg.linear_value_heads % cfg.linear_key_heads:
        raise ValueError("every key head must feed the same number of value heads")
    if cfg.qk_rope_head_dim % 2:
        raise ValueError("RoPE rotates pairs")


# --------------------------------------------------------------------- //
# Parameters

_NORMS = ("n_mix_in", "n_mix_out", "n_mlp_in", "n_mlp_out")


def _shapes(cfg: GigaChat35Config, mixer: str, mlp: str) -> Dict[str, Tuple[Tuple[int, ...], str]]:
    """name -> (shape, kind) of one layer's leaves. kind: 'w' a bfloat16
    matrix (std 1/sqrt(fan_in)), or the name of a float32 leaf whose
    range ``init_params_fast`` gives."""
    D, H = cfg.hidden_size, cfg.num_heads
    s: Dict[str, Tuple[Tuple[int, ...], str]] = {}
    for n in _NORMS:
        s[f"{n}_w"] = ((D,), "norm_w")
        s[f"{n}_g"] = ((D,), "norm_g")
    if mixer == "gdn":
        Hv, Kv = cfg.linear_value_heads, cfg.value_dim
        s.update({
            "wqkv": ((D, cfg.conv_dim), "w"), "conv_w": ((cfg.linear_conv, cfg.conv_dim), "conv"),
            "wzba": ((D, Kv + 2 * Hv), "w"), "A_log": ((Hv,), "A_log"), "dt_bias": ((Hv,), "dt_bias"),
            "o_norm": ((cfg.linear_value_head_dim,), "norm_w"), "wo": ((Kv, D), "w"),
        })
    else:
        ql, R, dn, dr, Dv = (cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_head_dim,
                             cfg.qk_rope_head_dim, cfg.v_head_dim)
        s.update({
            # [cq | output gate | c_kv | k_r]: every split on a lane tile at the published widths
            "wx": ((D, ql + H * Dv + R + dr), "w"),
            "q_norm": ((ql,), "near_one"), "kv_norm": ((R,), "near_one"),
            "wcq": ((ql, H * (dn + dr)), "w"),
            "wuk": ((H, dn, R), "wuk"), "wuv": ((H, R, Dv), "wuv"),
            "wo": ((H * Dv, D), "w"),
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


def count_logical_params(cfg: GigaChat35Config) -> int:
    """Parameters this chip HOLDS (its experts, its vocabulary rows)."""
    n = sum(math.prod(shape) for mixer, mlp in cfg.layers for shape, _ in _shapes(cfg, mixer, mlp).values())
    return n + 2 * cfg.vocab_size * cfg.hidden_size + 2 * cfg.hidden_size


def init_params_fast(cfg: GigaChat35Config, seed: int = 0, dtype: jnp.dtype = jnp.bfloat16) -> Params:
    """Seeded random weights, drawn leaf by leaf ON the accelerator where
    there is one. Every term of the equations is drawn away from the
    value that would hide it: norm weights ``w`` N(0, 0.1) and gates
    ``g`` N(0, 0.5) (a ``1 + w`` at ``w = 0`` or a ``2 sigmoid(0)`` hides
    a dropped term), ``A_log`` = log U(1, 16), ``dt_bias`` so that
    softplus gives steps log-uniform in 1e-3..1e-1, the selection bias
    ``e_bias`` N(0, 0.1), the two plain RMSNorm weights 1 + N(0, 0.1)."""
    validate(cfg)
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
        if kind in ("wuk", "wuv"):  # [H, in, out]
            return normal(shape, 1 / math.sqrt(shape[1]))
        if kind == "conv":
            return normal(shape, 1 / math.sqrt(shape[0]), jnp.float32)
        if kind == "router":
            return normal(shape, 1 / math.sqrt(shape[0]), jnp.float32)
        if kind == "norm_w":
            return normal(shape, 0.1, jnp.float32)
        if kind == "norm_g":
            return normal(shape, 0.5, jnp.float32)
        if kind == "near_one":
            return normal(shape, 0.1, jnp.float32, mean=1.0)
        if kind == "e_bias":
            return normal(shape, 0.1, jnp.float32)
        if kind == "A_log":
            return jnp.log(jax.random.uniform(key(), shape, jnp.float32, 1.0, 16.0))
        if kind == "dt_bias":
            dt0 = jnp.exp(jax.random.uniform(key(), shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
            return dt0 + jnp.log(-jnp.expm1(-dt0))  # softplus^-1
        raise ValueError(kind)

    with jax.default_device(jax.devices()[0]):  # the accelerator where there is one
        layers = [{name: leaf(shape, kind) for name, (shape, kind) in _shapes(cfg, mixer, mlp).items()}
                  for mixer, mlp in cfg.layers]
        D = cfg.hidden_size
        return {
            "embed": normal((cfg.vocab_size, D), 1.0),
            "head": normal((D, cfg.vocab_size), 1 / math.sqrt(D)),
            "final_norm_w": leaf((D,), "norm_w"), "final_norm_g": leaf((D,), "norm_g"),
            "layers": layers,
        }


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _draw(key, shape, std, mean, dtype_name):
    return (jax.random.normal(key, shape, jnp.float32) * std + mean).astype(jnp.dtype(dtype_name))


# --------------------------------------------------------------------- //
# Caches and the memory plan


def init_paged_cache(cfg: GigaChat35Config, pool_pages: int, page_size: int, num_slots: int,
                     dtype: jnp.dtype = jnp.bfloat16) -> Caches:
    Hv, Dk, Dv = cfg.linear_value_heads, cfg.linear_key_head_dim, cfg.linear_value_head_dim
    n_gdn, n_mla = len(cfg.layers_of("gdn")), len(cfg.layers_of("mla"))
    return {
        "lat": [jnp.zeros((pool_pages, page_size, cfg.latent_row), dtype) for _ in range(n_mla)],
        "gdn": [jnp.zeros((num_slots, Hv, Dk, Dv), jnp.float32) for _ in range(n_gdn)],
        "conv": [jnp.zeros((num_slots, cfg.linear_conv - 1, cfg.conv_dim), dtype) for _ in range(n_gdn)],
        "stats": jnp.zeros((len(STAT_NAMES),), jnp.int32),
    }


def kv_bytes_per_token(cfg: GigaChat35Config, kv_bytes: float = 2) -> int:
    """Paged bytes a cached token costs, AS ALLOCATED: the padded row of
    each latent-attention layer."""
    return int(len(cfg.layers_of("mla")) * cfg.latent_row * kv_bytes)


def fixed_state_bytes_per_slot(cfg: GigaChat35Config, kv_bytes: float = 2) -> int:
    gdn = (cfg.linear_value_heads * cfg.linear_key_head_dim * cfg.linear_value_head_dim * 4
           + (cfg.linear_conv - 1) * cfg.conv_dim * kv_bytes)
    return int(len(cfg.layers_of("gdn")) * gdn)


def serving_memory_bytes(cfg: GigaChat35Config, batch: int, max_seq_len: int,
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


def gated_norm(x, w, g, eps: float, gate_scale: float):
    """The zero-centred gated norm, float32:
    ``x / sqrt(mean(x^2) + eps) * (1 + w) * gate_scale sigmoid(g)``."""
    x32 = x.astype(jnp.float32)
    y = x32 * lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return y * ((1.0 + w.astype(jnp.float32)) * (gate_scale * jax.nn.sigmoid(g.astype(jnp.float32))))


def _norm(x, lp: Params, name: str, cfg: GigaChat35Config):
    return gated_norm(x, lp[f"{name}_w"], lp[f"{name}_g"], cfg.norm_eps, cfg.norm_gate_scale)


def sublayer(x, lp: Params, sub: str, cfg: GigaChat35Config, fn):
    """``x + N_out(fn(N_in(x)))``; x float32 [.., D]."""
    return x + _norm(fn(_norm(x, lp, f"n_{sub}_in", cfg)), lp, f"n_{sub}_out", cfg)


def mlp_sublayer(x, lp: Params, mlp: str, cfg: GigaChat35Config, count, kernel: Optional[str]):
    """The MLP sublayer over x [.., D]; returns (x, moe stats or None)."""
    box = []

    def fn(u):
        if mlp == "dense":
            return swiglu_mlp(u, lp["w_gate_up"], lp["w_down"], cfg.swiglu_limit)
        with jax.named_scope("experts"):
            y, stats = moe(u.reshape(-1, u.shape[-1]), lp, cfg, count.reshape(-1), kernel)
        box.append(stats)
        return y.reshape(u.shape[:-1] + (y.shape[-1],))

    x = sublayer(x, lp, "mlp", cfg, fn)
    return x, (box[0] if box else None)


def head(params: Params, cfg: GigaChat35Config, hidden):
    """hidden [N, D] -> float32 logits [N, V]."""
    h = gated_norm(hidden, params["final_norm_w"], params["final_norm_g"], cfg.norm_eps, cfg.norm_gate_scale)
    return _mm(h, params["head"])


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(cfg):
    """YaRN's frequencies of the RoPE key's pairs: the published ones
    where a pair turns more than ``beta_fast`` times inside the original
    context, divided by ``factor`` where it turns less than ``beta_slow``
    times, a linear ramp between. numpy-free constants, float32."""
    dim, base = cfg.qk_rope_head_dim, cfg.rope_theta

    def correction_dim(rotations: float) -> float:
        return dim * math.log(cfg.rope_original_max / (rotations * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(correction_dim(cfg.rope_beta_fast)), 0)
    high = min(math.ceil(correction_dim(cfg.rope_beta_slow)), dim - 1)
    span = max(high - low, 1e-3)
    out = []
    for i in range(dim // 2):
        extra = base ** (-2.0 * i / dim)
        ramp = min(max((i - low) / span, 0.0), 1.0)
        out.append(extra / cfg.rope_factor * ramp + extra * (1.0 - ramp))
    return jnp.asarray(out, jnp.float32)


def rope(x, positions, cfg):
    """Interleaved RoPE (pairs 2i, 2i+1) over the whole last axis, at
    YaRN's frequencies; cos and sin unscaled (``mscale == mscale_all_dim``).
    x [.., T, (h,) dr] float32, positions [.., T]."""
    ratio = yarn_mscale(cfg.rope_factor, cfg.rope_mscale) / yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim)
    ang = positions.astype(jnp.float32)[..., None] * yarn_inv_freq(cfg)
    if x.ndim == ang.ndim + 1:
        ang = ang[..., None, :]
    cos, sin = jnp.cos(ang) * ratio, jnp.sin(ang) * ratio
    pairs = x.reshape(x.shape[:-1] + (x.shape[-1] // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1).reshape(x.shape)


# --------------------------------------------------------------------- //
# Gated DeltaNet


def _gdn_inputs(x, conv_cat, lp: Params, cfg: GigaChat35Config):
    """From the normed input x [.., T, D] and the convolution's input
    ``conv_cat`` [.., T + conv - 1, 2Kk + Kv] (the tail, then this call's
    projections): q, k [.., T, Hv, Dk] (each key head repeated for the
    value heads it feeds), v [.., T, Hv, Dv], beta and the log decay g
    (<= 0) [.., T, Hv], the output gate's input z [.., T, Hv, Dv]. float32."""
    Hk, Hv, Dk, Dv = cfg.linear_key_heads, cfg.linear_value_heads, cfg.linear_key_head_dim, cfg.linear_value_head_dim
    Kk, Kv = cfg.key_dim, cfg.value_dim
    T = x.shape[-2]
    w = lp["conv_w"]
    qkv = sum(lax.slice_in_dim(conv_cat, i, i + T, axis=conv_cat.ndim - 2) * w[i] for i in range(cfg.linear_conv))
    qkv = jax.nn.silu(qkv)
    q = qkv[..., :Kk].reshape(qkv.shape[:-1] + (Hk, Dk))
    k = qkv[..., Kk:2 * Kk].reshape(qkv.shape[:-1] + (Hk, Dk))
    v = qkv[..., 2 * Kk:].reshape(qkv.shape[:-1] + (Hv, Dv))
    q = q * lax.rsqrt(jnp.sum(q * q, axis=-1, keepdims=True) + 1e-6) * (Dk ** -0.5)
    k = k * lax.rsqrt(jnp.sum(k * k, axis=-1, keepdims=True) + 1e-6)
    q, k = (jnp.repeat(t, Hv // Hk, axis=-2) for t in (q, k))  # value head j reads key head j // (Hv / Hk)
    zba = _mm(x, lp["wzba"])
    z = zba[..., :Kv].reshape(zba.shape[:-1] + (Hv, Dv))
    beta = jax.nn.sigmoid(zba[..., Kv:Kv + Hv])
    g = -jnp.exp(lp["A_log"]) * jax.nn.softplus(zba[..., Kv + Hv:] + lp["dt_bias"])
    return q, k, v, beta, g, z


def _gdn_output(o, z, lp: Params, cfg: GigaChat35Config):
    """``W_o [RMSNorm_128(o_j) (1 + w_o) 2 sigmoid(z_j)]``; o, z [.., Hv, Dv]."""
    y = gated_norm(o, lp["o_norm"], z, cfg.o_norm_eps, cfg.linear_gate_scale)
    return _mm(y.reshape(y.shape[:-2] + (cfg.value_dim,)), lp["wo"])


def gdn_step(S, q, k, v, beta, g):
    """One token of the gated delta rule. S [.., Dk, Dv]; q, k [.., Dk];
    v [.., Dv]; beta, g [..]. Returns (o [.., Dv], S).
    ``S <- e^g S; S <- S + beta k (v - S^T k)^T; o = S^T q``, arranged so
    that the old state is read once for both products and written once."""
    a = jnp.exp(g)[..., None]
    both = jnp.stack([k, q], axis=-2)  # [.., 2, Dk]
    red = a[..., None] * jnp.sum(S[..., None, :, :] * both[..., None], axis=-2)  # [.., 2, Dv]
    u = beta[..., None] * (v - red[..., 0, :])
    o = red[..., 1, :] + jnp.sum(k * q, axis=-1, keepdims=True) * u
    return o, a[..., None] * S + k[..., None] * u[..., None, :]


def gdn_chunk(S, q, k, v, beta, g, block: int = GDN_BLOCK):
    """The same recurrence over T tokens, block-wise (WY / UT transform).
    S [N, H, Dk, Dv]; q, k [N, T, H, Dk]; v [N, T, H, Dv]; beta, g
    [N, T, H]. A token with beta = 0 and g = 0 leaves the state as it is.
    Returns (o [N, T, H, Dv], S).

    Inside a block, with ``G`` the cumulative log decay from the block's
    start and ``L_ij = e^(G_i - G_j)`` for ``i >= j`` (never above 1):
    ``A = strict_tril(beta (k k^T) L)``, ``T = (I + A)^-1``,
    ``U = T beta v - T (beta k e^G) S``, ``O = (q e^G) S + tril((q k^T) L) U``,
    ``S <- e^G_end S + (k e^(G_end - G))^T U``."""
    N, T, H, Dk = q.shape
    B = min(block, T)
    nb = T // B
    assert nb * B == T, (T, B)

    def blocks(x):  # [N, T, H, D] -> [nb, N, H, B, D]
        return jnp.transpose(x.reshape(N, nb, B, H, x.shape[-1]), (1, 0, 3, 2, 4))

    qb, kb, vb = blocks(q), blocks(k), blocks(v)
    bb, gb = (jnp.transpose(x.reshape(N, nb, B, H), (1, 0, 3, 2)) for x in (beta, g))  # [nb, N, H, B]
    G = jnp.cumsum(gb, axis=-1)
    tril = jnp.tril(jnp.ones((B, B), bool))
    L = jnp.exp(jnp.where(tril, G[..., :, None] - G[..., None, :], -jnp.inf))  # 0 above the diagonal
    kk = jnp.einsum("...tk,...ik->...ti", kb, kb, precision=_HI)
    A = jnp.where(jnp.tril(tril, -1), kk * L * bb[..., None], 0.0)
    # (I + A)^-1 = (I - A)(I + A^2)(I + A^4)...: A is strictly lower, so A^B = 0
    eye = jnp.eye(B, dtype=jnp.float32)
    Tm, P = eye - A, jnp.matmul(A, A, precision=_HI)
    for _ in range(max(0, (B - 1).bit_length() - 1)):
        Tm = jnp.matmul(Tm, eye + P, precision=_HI)
        P = jnp.matmul(P, P, precision=_HI)
    eG = jnp.exp(G)[..., None]
    Wv = jnp.matmul(Tm, bb[..., None] * vb, precision=_HI)
    Wk = jnp.matmul(Tm, bb[..., None] * kb * eG, precision=_HI)
    Pq = jnp.einsum("...tk,...ik->...ti", qb, kb, precision=_HI) * L
    qg = qb * eG
    k_end = kb * jnp.exp(G[..., -1:] - G)[..., None]
    g_end = jnp.exp(G[..., -1])  # [nb, N, H]

    def body(S, xs):
        Wv_b, Wk_b, qg_b, Pq_b, ke_b, ge_b = xs
        U = Wv_b - jnp.matmul(Wk_b, S, precision=_HI)
        O = jnp.matmul(qg_b, S, precision=_HI) + jnp.matmul(Pq_b, U, precision=_HI)
        S = ge_b[..., None, None] * S + jnp.einsum("...tk,...tv->...kv", ke_b, U, precision=_HI)
        return S, O

    S, O = lax.scan(body, S, (Wv, Wk, qg, Pq, k_end, g_end))
    O = jnp.transpose(O, (1, 0, 3, 2, 4))  # [nb, N, H, B, Dv] -> [N, nb, B, H, Dv]
    return O.reshape(N, T, H, O.shape[-1]), S


# --------------------------------------------------------------------- //
# Latent attention


def _mla_project(x, positions, lp: Params, cfg, output_gate: bool = True):
    """x [.., T, D] normed -> per-head queries q_nope [.., T, H, dn] and
    q_rope [.., T, H, dr] (rotated), the output gate [.., T, H * Dv] (None
    for a model without one, ``output_gate`` False: ``wx`` then holds no
    gate columns), the row to cache ``[c | k_rope | 0]`` [.., T, row].
    float32."""
    H, ql, R = cfg.num_heads, cfg.q_lora_rank, cfg.kv_lora_rank
    dn, dr, Dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    xp = _mm(x, lp["wx"])
    cq = rms_norm(xp[..., :ql], lp["q_norm"], cfg.norm_eps, lp["wcq"].dtype)
    at = ql + (H * Dv if output_gate else 0)  # where [c_kv | k_r] starts
    gate = jax.nn.sigmoid(xp[..., ql:at]) if output_gate else None
    c = rms_norm(xp[..., at:at + R], lp["kv_norm"], cfg.norm_eps, jnp.float32)
    k_rope = rope(xp[..., at + R:], positions, cfg)
    q = _mm(cq, lp["wcq"]).reshape(xp.shape[:-1] + (H, dn + dr))
    pad = jnp.zeros(c.shape[:-1] + (cfg.latent_row - R - dr,), jnp.float32)
    return q[..., :dn], rope(q[..., dn:], positions, cfg), gate, jnp.concatenate([c, k_rope, pad], axis=-1)


def _absorb(q_nope, q_rope, lp: Params, cfg):
    """``[W_uk^T q_nope | q_rope | 0]`` [.., H, row]: the query against a cached row."""
    qlat = jnp.einsum("...hd,hdr->...hr", q_nope.astype(lp["wuk"].dtype), lp["wuk"], preferred_element_type=jnp.float32)
    pad = jnp.zeros(qlat.shape[:-1] + (cfg.latent_row - qlat.shape[-1] - q_rope.shape[-1],), jnp.float32)
    return jnp.concatenate([qlat, q_rope, pad], axis=-1)


def _mla_output(o, gate, lp: Params, cfg):
    """o [.., H, Dv] -> the mixer's output [.., D]: gated per channel
    (``gate`` None: no gate), then ``W_o``."""
    o = o.reshape(o.shape[:-2] + (cfg.num_heads * cfg.v_head_dim,))
    return _mm(o if gate is None else o * gate, lp["wo"])


def _attend_absorbed(q_nope, q_rope, lat_pool, tables, positions, lp: Params, cfg,
                     page_kernel: Optional[str] = None, work=None):
    """One decode query a row against its cached rows, ABSORBED: the
    query ``[W_uk^T q_nope | q_rope]`` against a row as it is cached, the
    softmax's sum of the rows' first ``kv_lora_rank`` columns through
    ``W_uv``. ``page_kernel`` ('compiled' / 'interpret') reads the pool
    through ``ops/latent_attention.py`` over the page work list ``work``;
    None gathers each row's whole table. q_nope [B, H, dn], q_rope
    [B, H, dr] float32; tables [B, Pmax]; positions [B]. Returns
    [B, H, Dv] float32."""
    B, R = q_nope.shape[0], cfg.kv_lora_rank
    qlat = _absorb(q_nope, q_rope, lp, cfg).astype(lat_pool.dtype)
    if page_kernel:
        acc = latent_attention.dense_latent_attention(
            qlat, lat_pool, tables, positions, value_dim=R, scale=cfg.softmax_scale,
            interpret=(page_kernel == "interpret"), work=work)
    else:
        S = tables.shape[1] * lat_pool.shape[1]
        rows = lat_pool[tables].reshape(B, S, lat_pool.shape[-1])
        sc = jnp.einsum("bhr,bsr->bhs", qlat, rows, preferred_element_type=jnp.float32) * cfg.softmax_scale
        ok = jnp.arange(S, dtype=jnp.int32)[None, :] <= positions[:, None]
        p = jax.nn.softmax(jnp.where(ok[:, None], sc, _NEG), axis=-1)
        acc = jnp.einsum("bhs,bsr->bhr", p.astype(rows.dtype), rows[..., :R], preferred_element_type=jnp.float32)
    return jnp.einsum("bhr,hrv->bhv", acc.astype(lp["wuv"].dtype), lp["wuv"], preferred_element_type=jnp.float32)


def latent_chunk_kind(cfg, kind: Optional[str], chunk: Optional[int] = None,
                      page_size: Optional[int] = None) -> Optional[str]:
    """``kind`` where ``ops/latent_attention.py`` ``latent_chunk_read``
    tiles this configuration's widths (and a chunk width and page size,
    where the caller knows them), else None: decided from the shapes."""
    ok = kind and latent_attention.chunk_read_supported(
        cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim, cfg.kv_lora_rank, cfg.latent_row,
        chunk, page_size)
    return kind if ok else None


def _attend_expanded(q_nope, q_rope, lat_pool, pages, positions, n_tokens, lp: Params,
                     cfg, block_pages: int = 4, latent_chunk: Optional[str] = None, work=None):
    """Chunk attention over a row's pages with a running softmax, in
    blocks of ``block_pages`` pages, as far as ``n_tokens`` [N] reach: ONE
    program whatever the context. Each block's per-head keys and values
    are rebuilt from its latent rows (``latent_expand``).
    ``latent_chunk`` ('compiled' / 'interpret') does it in
    ``ops/latent_attention.py`` ``latent_chunk_read``, where neither they
    nor a score leaves the chip, over the block work list ``work``; None
    in the XLA loop below. q_nope [N, T, H, dn], q_rope [N, T, H, dr]
    float32; pages [N, Pmax]; positions [N, T]. Returns [N, T, H, Dv]
    float32."""
    if latent_chunk:
        return latent_attention.latent_chunk_read(
            jnp.moveaxis(q_nope, 2, 1), jnp.moveaxis(q_rope, 2, 1), lat_pool, pages, positions, n_tokens,
            lp["wuk"], lp["wuv"], scale=cfg.softmax_scale, interpret=(latent_chunk == "interpret"), work=work)
    N, T, H, _ = q_nope.shape
    R, dr, Dv = cfg.kv_lora_rank, cfg.qk_rope_head_dim, cfg.v_head_dim
    page = lat_pool.shape[1]
    Pmax = pages.shape[1]
    bp = min(block_pages, Pmax)
    while Pmax % bp:
        bp -= 1
    W = bp * page
    n_blocks = jnp.max((n_tokens + W - 1) // W)
    dt = lat_pool.dtype
    qn = jnp.moveaxis(q_nope, 2, 1).astype(dt)  # [N, H, T, dn]
    qr = jnp.moveaxis(q_rope, 2, 1).astype(dt)
    scale = cfg.softmax_scale

    def body(i, carry):
        m, l, acc = carry
        pg = lax.dynamic_slice_in_dim(pages, i * bp, bp, axis=1)
        rows = lat_pool[pg].reshape(N, W, lat_pool.shape[-1])
        with jax.named_scope("latent_expand"):
            c = rows[..., :R]
            kn = jnp.einsum("nsr,hdr->nhsd", c, lp["wuk"].astype(dt), preferred_element_type=jnp.float32).astype(dt)
            vh = jnp.einsum("nsr,hrv->nhsv", c, lp["wuv"].astype(dt), preferred_element_type=jnp.float32).astype(dt)
        sc = (jnp.einsum("nhtd,nhsd->nhts", qn, kn, preferred_element_type=jnp.float32)
              + jnp.einsum("nhtd,nsd->nhts", qr, rows[..., R:R + dr], preferred_element_type=jnp.float32)) * scale
        ok = (i * W + jnp.arange(W, dtype=jnp.int32))[None, None, :] <= positions[:, :, None]  # [N, T, W]
        ok = ok[:, None]
        sc = jnp.where(ok, sc, _NEG)
        m_new = jnp.maximum(m, jnp.max(sc, axis=-1, keepdims=True))
        p = jnp.where(ok, jnp.exp(sc - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        l = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        acc = alpha * acc + jnp.einsum("nhts,nhsv->nhtv", p.astype(dt), vh, preferred_element_type=jnp.float32)
        return m_new, l, acc

    init = (jnp.full((N, H, T, 1), _NEG, jnp.float32), jnp.zeros((N, H, T, 1), jnp.float32),
            jnp.zeros((N, H, T, Dv), jnp.float32))
    _, l, acc = lax.fori_loop(0, n_blocks, body, init)
    return jnp.moveaxis(acc / jnp.where(l == 0.0, 1.0, l), 1, 2)


def chunk_read_stats(layers: int, latent_chunk: Optional[str]):
    """``[latent_chunk_kernel_layers, latent_chunk_xla_layers]`` of one
    chunk walk over ``layers`` latent layers: which path read them."""
    return jnp.asarray([layers, 0] if latent_chunk else [0, layers], jnp.int32)


def _stats(moe_stats, latent_read, chunk_layers=(0, None), kernel_rows=0):
    """The walk's counts in ``STAT_NAMES``' order."""
    return jnp.concatenate([moe_stats, latent_read[None], chunk_read_stats(*chunk_layers),
                            jnp.asarray(kernel_rows, jnp.int32)[None]]).astype(jnp.int32)


# --------------------------------------------------------------------- //
# The chunk walk: prefill and chunked extend


def _chunk_walk(params: Params, cfg: GigaChat35Config, caches: Caches, tokens, offsets, valid, slots,
                tables, page_size: int, grouped_matmul: Optional[str] = None, latent_chunk: Optional[str] = None):
    """All layers over a chunk [N, C] per row; returns (the residual row
    of each row's last valid position [N, D], caches).

    A row at ``offsets == 0`` starts from a zero state (which is what
    resets a slot at admission); a row at ``offsets > 0`` carries its
    slot's state on. A row with ``valid == 0`` changes nothing: its pool
    writes are dropped and its slot's state is written back as it was.
    The latent read walks each row's pages as far as its context reaches
    whatever window the engine names: one program a chunk width.
    ``latent_chunk`` ('compiled' / 'interpret') reads them through
    ``ops/latent_attention.py`` ``latent_chunk_read`` where the shapes
    tile; else, and where None, through the XLA loop."""
    N, C = tokens.shape
    S = tables.shape[1] * page_size
    idx = jnp.arange(C, dtype=jnp.int32)
    positions = jnp.minimum(offsets[:, None] + idx[None, :], S - 1)
    tok_valid = idx[None, :] < valid[:, None]
    row_live = valid > 0
    started = row_live & (offsets > 0)
    last = jnp.clip(valid, 1, C) - 1
    row_tables = tables[slots]
    P = caches["lat"][0].shape[0] if caches["lat"] else 0
    n_tokens = jnp.where(row_live, offsets + valid, 0)
    latent_chunk = latent_chunk_kind(cfg, latent_chunk, C, page_size) if P else None
    # one work list a chunk: every latent layer walks the same blocks
    work = latent_attention.chunk_work_list(row_tables, n_tokens, page_size, P) if latent_chunk else None

    x = params["embed"][tokens].astype(jnp.float32)  # [N, C, D]
    new = {k: list(v) if isinstance(v, list) else v for k, v in caches.items()}
    moe_stats = jnp.zeros((len(MOE_STAT_NAMES),), jnp.int32)
    latent_read = jnp.zeros((), jnp.int32)
    i_gdn = i_mla = 0
    for l, (mixer, mlp) in enumerate(cfg.layers):
        lp = params["layers"][l]
        if mixer == "gdn":
            i = i_gdn
            i_gdn += 1

            def mix(u, lp=lp, i=i):
                with jax.named_scope("gdn_chunk"):
                    old_S, old_tail = caches["gdn"][i][slots], caches["conv"][i][slots]
                    proj = _mm(u, lp["wqkv"])
                    tail = jnp.where(started[:, None, None], old_tail.astype(jnp.float32), 0.0)
                    cat = jnp.concatenate([tail, proj], axis=1)
                    q, k, v, beta, g, z = _gdn_inputs(u, cat, lp, cfg)
                    beta = jnp.where(tok_valid[..., None], beta, 0.0)
                    g = jnp.where(tok_valid[..., None], g, 0.0)
                    S0 = jnp.where(started[:, None, None, None], old_S, 0.0).astype(jnp.float32)
                    o, S1 = gdn_chunk(S0, q, k, v, beta, g)
                    taps = valid[:, None] + jnp.arange(cfg.linear_conv - 1, dtype=jnp.int32)[None, :]
                    new_tail = jnp.take_along_axis(cat, taps[:, :, None], axis=1).astype(old_tail.dtype)
                    keep = row_live[:, None, None]
                    new["gdn"][i] = caches["gdn"][i].at[slots].set(
                        jnp.where(keep[..., None], S1.astype(old_S.dtype), old_S))
                    new["conv"][i] = caches["conv"][i].at[slots].set(jnp.where(keep, new_tail, old_tail))
                    return _gdn_output(o, z, lp, cfg)
        else:
            i = i_mla
            i_mla += 1

            def mix(u, lp=lp, i=i):
                nonlocal latent_read
                with jax.named_scope("latent_read"):
                    q_nope, q_rope, gate, row = _mla_project(u, positions, lp, cfg)
                    phys = jnp.take_along_axis(row_tables, positions // page_size, axis=1)
                    phys = jnp.where(tok_valid, phys, P)  # padding: dropped
                    lat = _write_rows(caches["lat"][i], phys, positions % page_size, row)
                    new["lat"][i] = lat
                    latent_read = latent_read + jnp.sum(jnp.where(tok_valid, positions + 1, 0))
                    o = _attend_expanded(q_nope, q_rope, lat, row_tables, positions, n_tokens, lp, cfg,
                                         latent_chunk=latent_chunk, work=work)
                    return _mla_output(o, gate, lp, cfg)

        x = sublayer(x, lp, "mix", cfg, mix)
        x, stats = mlp_sublayer(x, lp, mlp, cfg, tok_valid, grouped_matmul)
        if stats is not None:
            moe_stats = moe_stats + stats
    # the block-wise recurrence advanced every state: the step kernel none
    new["stats"] = _stats(moe_stats, latent_read, chunk_layers=(i_mla, latent_chunk))
    return jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0], new


def prefill_paged(params: Params, cfg: GigaChat35Config, caches: Caches, tokens, lengths, slots, tables,
                  page_size: int, grouped_matmul: Optional[str] = None, delta_step: Optional[str] = None,
                  latent_chunk: Optional[str] = None, **_paths):
    """A monolithic admission wave: (last-position logits [N, V], caches)."""
    del delta_step  # the step kernel serves decode; a chunk walks block-wise
    hidden, caches = _chunk_walk(params, cfg, caches, tokens, jnp.zeros_like(lengths), lengths, slots,
                                 tables, page_size, grouped_matmul, latent_chunk)
    return head(params, cfg, hidden), caches


def extend_paged(params: Params, cfg: GigaChat35Config, caches: Caches, tokens, offsets, valid, slots,
                 tables, window: int, page_size: int, grouped_matmul: Optional[str] = None,
                 delta_step: Optional[str] = None, latent_chunk: Optional[str] = None, **_paths):
    """One chunk of a chunked prefill: (the residual row [N, D] of each
    row's last valid position, caches)."""
    del window, delta_step  # the latent read follows each row's own context; a chunk walks block-wise
    return _chunk_walk(params, cfg, caches, tokens, offsets, valid, slots, tables, page_size, grouped_matmul,
                       latent_chunk)


# --------------------------------------------------------------------- //
# One decode step


def decode_paged(params: Params, cfg: GigaChat35Config, caches: Caches, tokens, positions, live, tables,
                 window: Optional[int], page_size: int, page_kernel: Optional[str] = None,
                 grouped_matmul: Optional[str] = None, delta_step: Optional[str] = None,
                 latent_chunk: Optional[str] = None, **_paths):
    """One token per slot: (logits [B, V], caches). A dead row leaves
    every fixed state as it is and writes nothing to the pools.
    ``delta_step`` ('compiled' / 'interpret') advances the delta-rule
    state with ``ops/delta_rule.py``, in place; None with ``gdn_step``."""
    del window, latent_chunk  # a step reads absorbed: no chunk read
    B = tokens.shape[0]
    S = tables.shape[1] * page_size
    R = cfg.kv_lora_rank
    P = caches["lat"][0].shape[0] if caches["lat"] else 0
    phys = jnp.where(live, jnp.take_along_axis(tables, (positions // page_size)[:, None], axis=1)[:, 0], P)
    # one work list a step, the pages a grid step that the kernel's rule names
    work = (latent_attention.decode_work_list(caches["lat"][0], tables, positions)
            if page_kernel and caches["lat"] else None)

    x = params["embed"][tokens].astype(jnp.float32)  # [B, D]
    new = {k: list(v) if isinstance(v, list) else v for k, v in caches.items()}
    moe_stats = jnp.zeros((len(MOE_STAT_NAMES),), jnp.int32)
    latent_read = jnp.zeros((), jnp.int32)
    i_gdn = i_mla = 0
    for l, (mixer, mlp) in enumerate(cfg.layers):
        lp = params["layers"][l]
        if mixer == "gdn":
            i = i_gdn
            i_gdn += 1

            def mix(u, lp=lp, i=i):
                with jax.named_scope("gdn_step"):
                    old_S, old_tail = caches["gdn"][i], caches["conv"][i]
                    proj = _mm(u, lp["wqkv"])
                    cat = jnp.concatenate([old_tail.astype(jnp.float32), proj[:, None]], axis=1)
                    q, k, v, beta, g, z = _gdn_inputs(u[:, None], cat, lp, cfg)
                    keep = live[:, None, None]
                    if delta_step:
                        # the kernel maps a key head to the value heads it feeds: it takes each key head
                        # once (repeat and slice fuse), and the one-a-head decay broadcast per channel
                        ratio = cfg.linear_value_heads // cfg.linear_key_heads
                        decay = jnp.broadcast_to(g[:, 0, :, None], g.shape[:1] + old_S.shape[1:3])
                        o, new["gdn"][i] = delta_rule.delta_rule_step(
                            old_S, q[:, 0, ::ratio], k[:, 0, ::ratio], v[:, 0], beta[:, 0], decay, live,
                            interpret=(delta_step == "interpret"))
                    else:
                        o, S1 = gdn_step(old_S.astype(jnp.float32), q[:, 0], k[:, 0], v[:, 0], beta[:, 0], g[:, 0])
                        new["gdn"][i] = jnp.where(keep[..., None], S1.astype(old_S.dtype), old_S)
                    new["conv"][i] = jnp.where(keep, cat[:, 1:].astype(old_tail.dtype), old_tail)
                    return _gdn_output(o, z[:, 0], lp, cfg)
        else:
            i = i_mla
            i_mla += 1

            def mix(u, lp=lp, i=i):
                nonlocal latent_read
                with jax.named_scope("latent_read"):
                    q_nope, q_rope, gate, row = _mla_project(u[:, None], positions[:, None], lp, cfg)
                    lat = _write_rows(caches["lat"][i], phys, positions % page_size, row[:, 0])
                    new["lat"][i] = lat
                    latent_read = latent_read + jnp.sum(jnp.where(live, positions + 1, 0))
                    o = _attend_absorbed(q_nope[:, 0], q_rope[:, 0], lat, tables, positions, lp, cfg, page_kernel, work)
                    return _mla_output(o, gate[:, 0], lp, cfg)

        x = sublayer(x, lp, "mix", cfg, mix)
        x, stats = mlp_sublayer(x, lp, mlp, cfg, live, grouped_matmul)
        if stats is not None:
            moe_stats = moe_stats + stats
    kernel_rows = jnp.sum(live.astype(jnp.int32)) if delta_step and i_gdn else 0
    new["stats"] = _stats(moe_stats, latent_read, kernel_rows=kernel_rows)
    return head(params, cfg, x), new
