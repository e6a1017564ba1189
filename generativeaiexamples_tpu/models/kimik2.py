"""Kimi-K2.5 (``kimi_k2``, 1.04T-A32B: DeepSeek-V3's layer) for the
serving engine, as the share ONE chip holds of an expert-parallel
deployment. The language model on text; the vision tower is not served.

One residual stream, plain pre-norm (``N(u) = u / sqrt(mean(u^2) + eps)
* w``, float32): ``h = x + Attn(N1(x))``, ``x' = h + MLP(N2(h))``, a
final ``N`` before the untied head.

- **Latent attention in EVERY layer** (MLA with a decoupled RoPE key):
  ``models/gigachat35.py``'s functions, called with the output gate
  absent (``_mla_project``, ``_attend_absorbed``, ``_mla_output``,
  ``_attend_expanded``, ``rope`` at YaRN's frequencies). The cache holds
  ONE row a token and layer, ``[c | k_rope | padding]`` (512 + 64 padded
  to 640 columns), the key of all 64 heads; the value is its first 512
  columns. Decode reads it ABSORBED (``ops/latent_attention.py``
  ``dense_latent_attention`` where the page kernel resolved), the chunk
  walk EXPANDED (per-head keys and values rebuilt from the latent a
  block of pages at a time).
- **MLP**: layer 0 a dense SwiGLU, every other layer
  ``models/glm5next.py``'s router and expert layer (a sigmoid router
  over all 384 experts, top 8 of score + bias, the chosen scores
  normalised and scaled, of which this chip HOLDS ``experts_held`` from
  ``experts_first`` on, plus the shared expert; no clamp;
  ``ops/grouped_matmul.py``). Pairs routed to absent experts are left
  out.

**One kind of cache** (docs/model_registry.md): the latent rows ``lat
[P, page, 640]`` of each layer, and nothing a slot: no layer keeps state
beside the pages, so the family registers ``fixed_state=False`` and the
prefix store shares its pages by refcount. ``stats`` is a handful of
int32 counts of the last walk; ``latent_tokens_read`` counts the cached
tokens ONE layer's read covered (every layer reads the same).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from generativeaiexamples_tpu.models.gigachat35 import (
    _attend_absorbed, _attend_expanded, _draw, _mla_output, _mla_project, chunk_read_stats, latent_chunk_kind,
    yarn_mscale,
)
from generativeaiexamples_tpu.models.glm5next import MOE_STAT_NAMES, _mm, _write_rows, moe, rms_norm, swiglu_mlp
from generativeaiexamples_tpu.ops import latent_attention

Params = Dict[str, Any]
Caches = Dict[str, Any]
_LANE = 128

STAT_NAMES = MOE_STAT_NAMES + ("latent_tokens_read", "latent_chunk_kernel_layers", "latent_chunk_xla_layers")


@dataclasses.dataclass(frozen=True)
class KimiK2Config:
    """Published widths; ``layers_served`` lists the published layers
    served (None: all); ``vocab_size``, ``experts_first`` and
    ``experts_held`` are this chip's share."""

    vocab_size: int = 163840
    hidden_size: int = 7168
    num_hidden_layers: int = 61
    layers_served: Optional[Tuple[int, ...]] = None
    first_k_dense_replace: int = 1
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    n_routed_experts: int = 384
    num_experts_per_tok: int = 8
    experts_first: int = 0
    experts_held: int = 384
    routed_scaling_factor: float = 2.827
    swiglu_limit: float = math.inf  # no clamp
    num_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 50000.0
    rope_factor: float = 64.0
    rope_original_max: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    norm_eps: float = 1e-5
    max_seq_len: int = 262144

    @property
    def layers(self) -> Tuple[str, ...]:
        """'dense' | 'sparse': the MLP of each layer SERVED."""
        served = range(self.num_hidden_layers) if self.layers_served is None else self.layers_served
        return tuple("dense" if l < self.first_k_dense_replace else "sparse" for l in served)

    @property
    def num_layers(self) -> int:
        return len(self.layers)

    @property
    def latent_row(self) -> int:
        """Columns of a cached row: latent and RoPE key, padded to whole lane tiles."""
        return -(-(self.kv_lora_rank + self.qk_rope_head_dim) // _LANE) * _LANE

    @property
    def softmax_scale(self) -> float:
        """DeepSeek-V3's YaRN rule: ``(dn + dr)^-0.5 * mscale(factor, mscale_all_dim)^2``."""
        m = yarn_mscale(self.rope_factor, self.rope_mscale_all_dim)
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5 * m * m


PRESETS: Dict[str, KimiK2Config] = {
    # one chip's share of the 32-way expert-parallel deployment: the leading
    # dense layer and four expert layers, 12 of 384 experts, an eighth of the vocabulary
    "kimi-k2.5-ep32": KimiK2Config(
        vocab_size=20480, layers_served=(0, 1, 2, 3, 4), experts_held=12, max_seq_len=24576),
    # CPU tests: one dense and two expert layers at a size a test checks by hand
    "kimik2-debug": KimiK2Config(
        vocab_size=256, hidden_size=64, num_hidden_layers=6, layers_served=(0, 1, 2),
        intermediate_size=96, moe_intermediate_size=32, n_routed_experts=16, num_experts_per_tok=4,
        experts_held=2, num_heads=4, q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, rope_original_max=64, max_seq_len=1024,
    ),
}


def validate(cfg: KimiK2Config) -> None:
    for l in cfg.layers_served or ():
        if not 0 <= l < cfg.num_hidden_layers:
            raise ValueError(f"layers_served names layer {l} of {cfg.num_hidden_layers}")
    if cfg.experts_first < 0 or cfg.experts_first + cfg.experts_held > cfg.n_routed_experts:
        raise ValueError("the experts held must lie inside the routed experts")
    if cfg.qk_rope_head_dim % 2:
        raise ValueError("RoPE rotates pairs")


# --------------------------------------------------------------------- //
# Parameters


def _shapes(cfg: KimiK2Config, mlp: str) -> Dict[str, Tuple[Tuple[int, ...], str]]:
    """name -> (shape, kind) of one layer's leaves. kind: 'w' a bfloat16
    matrix (std 1/sqrt(fan_in)), 'o' one that writes the stream (scaled
    down by depth), or the name of a float32 leaf whose range
    ``init_params_fast`` gives."""
    D, H = cfg.hidden_size, cfg.num_heads
    ql, R, dn, dr, Dv = (cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_head_dim,
                         cfg.qk_rope_head_dim, cfg.v_head_dim)
    s: Dict[str, Tuple[Tuple[int, ...], str]] = {
        "ln_attn": ((D,), "near_one"), "ln_mlp": ((D,), "near_one"),
        # [cq | c_kv | k_r]: no output gate
        "wx": ((D, ql + R + dr), "w"),
        "q_norm": ((ql,), "near_one"), "kv_norm": ((R,), "near_one"),
        "wcq": ((ql, H * (dn + dr)), "w"),
        "wuk": ((H, dn, R), "wuk"), "wuv": ((H, R, Dv), "wuv"),
        "wo": ((H * Dv, D), "o"),
    }
    if mlp == "dense":
        F = cfg.intermediate_size
        s.update({"w_gate_up": ((D, 2 * F), "w"), "w_down": ((F, D), "o")})
    else:
        F, E = cfg.moe_intermediate_size, cfg.experts_held
        s.update({
            "router": ((D, cfg.n_routed_experts), "router"), "e_bias": ((cfg.n_routed_experts,), "e_bias"),
            "ws_gate_up": ((D, 2 * F), "w"), "ws_down": ((F, D), "o"),
            "we_gate_up": ((E, D, 2 * F), "w"), "we_down": ((E, F, D), "o"),
        })
    return s


def count_logical_params(cfg: KimiK2Config) -> int:
    """Parameters this chip HOLDS (its layers, its experts, its vocabulary rows)."""
    n = sum(math.prod(shape) for mlp in cfg.layers for shape, _ in _shapes(cfg, mlp).values())
    return n + 2 * cfg.vocab_size * cfg.hidden_size + cfg.hidden_size


def init_params_fast(cfg: KimiK2Config, seed: int = 0, dtype: jnp.dtype = jnp.bfloat16) -> Params:
    """Seeded random weights, drawn leaf by leaf ON the accelerator where
    there is one. Norm weights 1 + N(0, 0.1) (a dropped norm is not
    hidden), the selection bias ``e_bias`` N(0, 0.1) (it decides which
    experts are chosen and never weighs them), matrices that write the
    stream scaled down by depth."""
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
        if kind == "wuk":  # [H, dn, R]: k_nope_h = W_uk,h c contracts the latent
            return normal(shape, 1 / math.sqrt(shape[2]))
        if kind == "wuv":  # [H, R, Dv]
            return normal(shape, 1 / math.sqrt(shape[1]))
        if kind == "router":
            return normal(shape, 1 / math.sqrt(shape[0]), jnp.float32)
        if kind == "near_one":
            return normal(shape, 0.1, jnp.float32, mean=1.0)
        if kind == "e_bias":
            return normal(shape, 0.1, jnp.float32)
        raise ValueError(kind)

    with jax.default_device(jax.devices()[0]):  # the accelerator where there is one
        layers = [{name: leaf(shape, kind) for name, (shape, kind) in _shapes(cfg, mlp).items()}
                  for mlp in cfg.layers]
        D = cfg.hidden_size
        return {
            "embed": normal((cfg.vocab_size, D), 1 / math.sqrt(D)),
            "head": normal((D, cfg.vocab_size), 1 / math.sqrt(D)),
            "final_norm": leaf((D,), "near_one"),
            "layers": layers,
        }


# --------------------------------------------------------------------- //
# Caches and the memory plan


def init_paged_cache(cfg: KimiK2Config, pool_pages: int, page_size: int, num_slots: int,
                     dtype: jnp.dtype = jnp.bfloat16) -> Caches:
    del num_slots  # every layer's state is pages
    return {
        "lat": [jnp.zeros((pool_pages, page_size, cfg.latent_row), dtype) for _ in range(cfg.num_layers)],
        "stats": jnp.zeros((len(STAT_NAMES),), jnp.int32),
    }


def kv_bytes_per_token(cfg: KimiK2Config, kv_bytes: float = 2) -> int:
    """Paged bytes a cached token costs, AS ALLOCATED: the padded row of every layer."""
    return int(cfg.num_layers * cfg.latent_row * kv_bytes)


def serving_memory_bytes(cfg: KimiK2Config, batch: int, max_seq_len: int,
                         weight_bytes: int = 2, kv_bytes: float = 2) -> Dict[str, int]:
    weights = count_logical_params(cfg) * weight_bytes
    paged = batch * max_seq_len * kv_bytes_per_token(cfg, kv_bytes)
    return {"weights": weights, "kv_cache": paged, "fixed_state": 0, "total": weights + paged}


def read_stats(caches: Caches):
    return caches["stats"]


# --------------------------------------------------------------------- //
# Layer mathematics


def _norm(x, w, cfg: KimiK2Config):
    return rms_norm(x, w, cfg.norm_eps, jnp.float32)


def mlp_sublayer(x, lp: Params, mlp: str, cfg: KimiK2Config, count, kernel: Optional[str]):
    """``x + MLP(N2(x))`` over x [.., D] float32; returns (x, moe stats or None)."""
    u = _norm(x, lp["ln_mlp"], cfg)
    if mlp == "dense":
        return x + swiglu_mlp(u, lp["w_gate_up"], lp["w_down"], cfg.swiglu_limit), None
    with jax.named_scope("experts"):
        y, stats = moe(u.reshape(-1, u.shape[-1]), lp, cfg, count.reshape(-1), kernel)
    return x + y.reshape(x.shape), stats


def head(params: Params, cfg: KimiK2Config, hidden):
    """hidden [N, D] -> float32 logits [N, V]."""
    return _mm(rms_norm(hidden, params["final_norm"], cfg.norm_eps), params["head"])


def _stats(moe_stats, latent_read, chunk_layers=(0, None)):
    """The walk's counts in ``STAT_NAMES``' order."""
    return jnp.concatenate([moe_stats, latent_read[None], chunk_read_stats(*chunk_layers)]).astype(jnp.int32)


# --------------------------------------------------------------------- //
# The chunk walk: prefill and chunked extend


def _chunk_walk(params: Params, cfg: KimiK2Config, caches: Caches, tokens, offsets, valid, slots,
                tables, page_size: int, grouped_matmul: Optional[str] = None, latent_chunk: Optional[str] = None):
    """All layers over a chunk [N, C] per row; returns (the residual row
    of each row's last valid position [N, D], caches).

    A row's context is whatever its page table maps below ``offsets``:
    pages this row wrote, or pages a prefix entry shares with it. A row
    with ``valid == 0`` changes nothing: its pool writes are dropped.
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
    last = jnp.clip(valid, 1, C) - 1
    row_tables = tables[slots]
    P = caches["lat"][0].shape[0]
    phys = jnp.take_along_axis(row_tables, positions // page_size, axis=1)
    phys = jnp.where(tok_valid, phys, P)  # padding: dropped
    n_tokens = jnp.where(row_live, offsets + valid, 0)
    latent_chunk = latent_chunk_kind(cfg, latent_chunk, C, page_size)
    # one work list a chunk: every layer walks the same blocks
    work = latent_attention.chunk_work_list(row_tables, n_tokens, page_size, P) if latent_chunk else None

    x = params["embed"][tokens].astype(jnp.float32)  # [N, C, D]
    new = dict(caches, lat=list(caches["lat"]))
    moe_stats = jnp.zeros((len(MOE_STAT_NAMES),), jnp.int32)
    for l, mlp in enumerate(cfg.layers):
        lp = params["layers"][l]
        with jax.named_scope("latent_read"):
            q_nope, q_rope, _, row = _mla_project(
                _norm(x, lp["ln_attn"], cfg), positions, lp, cfg, output_gate=False)
            lat = new["lat"][l] = _write_rows(caches["lat"][l], phys, positions % page_size, row)
            o = _attend_expanded(q_nope, q_rope, lat, row_tables, positions, n_tokens, lp, cfg,
                                 latent_chunk=latent_chunk, work=work)
            x = x + _mla_output(o, None, lp, cfg)
        x, stats = mlp_sublayer(x, lp, mlp, cfg, tok_valid, grouped_matmul)
        if stats is not None:
            moe_stats = moe_stats + stats
    new["stats"] = _stats(moe_stats, jnp.sum(jnp.where(tok_valid, positions + 1, 0)), (cfg.num_layers, latent_chunk))
    return jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0], new


def prefill_paged(params: Params, cfg: KimiK2Config, caches: Caches, tokens, lengths, slots, tables,
                  page_size: int, grouped_matmul: Optional[str] = None, latent_chunk: Optional[str] = None,
                  **_paths):
    """A whole prompt in one program, the REFERENCE walk: (last-position logits [N, V], caches)."""
    hidden, caches = _chunk_walk(params, cfg, caches, tokens, jnp.zeros_like(lengths), lengths, slots,
                                 tables, page_size, grouped_matmul, latent_chunk)
    return head(params, cfg, hidden), caches


def extend_paged(params: Params, cfg: KimiK2Config, caches: Caches, tokens, offsets, valid, slots,
                 tables, window: int, page_size: int, grouped_matmul: Optional[str] = None,
                 latent_chunk: Optional[str] = None, **_paths):
    """One chunk of a chunked prefill: (the residual row [N, D] of each
    row's last valid position, caches)."""
    del window  # the latent read follows each row's own context
    return _chunk_walk(params, cfg, caches, tokens, offsets, valid, slots, tables, page_size, grouped_matmul,
                       latent_chunk)


# --------------------------------------------------------------------- //
# One decode step


def decode_paged(params: Params, cfg: KimiK2Config, caches: Caches, tokens, positions, live, tables,
                 window: Optional[int], page_size: int, page_kernel: Optional[str] = None,
                 grouped_matmul: Optional[str] = None, latent_chunk: Optional[str] = None, **_paths):
    """One token per slot: (logits [B, V], caches). A dead row writes
    nothing to the pools. ``page_kernel`` ('compiled' / 'interpret')
    reads the pools through ``ops/latent_attention.py``; None gathers."""
    del window, latent_chunk  # a step reads absorbed: no chunk read
    P = caches["lat"][0].shape[0]
    phys = jnp.where(live, jnp.take_along_axis(tables, (positions // page_size)[:, None], axis=1)[:, 0], P)
    # one work list a step, the pages a grid step that the kernel's rule names: every layer walks the same pages
    work = latent_attention.decode_work_list(caches["lat"][0], tables, positions) if page_kernel else None

    x = params["embed"][tokens].astype(jnp.float32)  # [B, D]
    new = dict(caches, lat=list(caches["lat"]))
    moe_stats = jnp.zeros((len(MOE_STAT_NAMES),), jnp.int32)
    for l, mlp in enumerate(cfg.layers):
        lp = params["layers"][l]
        with jax.named_scope("latent_read"):
            q_nope, q_rope, _, row = _mla_project(
                _norm(x, lp["ln_attn"], cfg)[:, None], positions[:, None], lp, cfg, output_gate=False)
            lat = new["lat"][l] = _write_rows(caches["lat"][l], phys, positions % page_size, row[:, 0])
            o = _attend_absorbed(q_nope[:, 0], q_rope[:, 0], lat, tables, positions, lp, cfg, page_kernel, work)
            x = x + _mla_output(o, None, lp, cfg)
        x, stats = mlp_sublayer(x, lp, mlp, cfg, live, grouped_matmul)
        if stats is not None:
            moe_stats = moe_stats + stats
    new["stats"] = _stats(moe_stats, jnp.sum(jnp.where(live, positions + 1, 0)))
    return head(params, cfg, x), new
