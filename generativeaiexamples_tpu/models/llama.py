"""Llama-family decoder, TPU-first functional JAX.

This is the in-repo replacement for the LLM the reference serves from the
external NIM / TensorRT-LLM container (reference: deploy/compose/
docker-compose-nim-ms.yaml:2-22; consumed through ``ChatNVIDIA`` at
RetrievalAugmentedGeneration/common/utils.py:265-288). Instead of an HTTP
hop to a CUDA engine, the model is a pure function over a parameter pytree,
compiled by XLA and sharded with ``jax.sharding.NamedSharding`` over a
``Mesh`` (see parallel/sharding.py) so tensor parallelism rides ICI
collectives rather than NCCL.

Design notes (TPU-first):
- ``forward`` (training, the tests' cache-free reference): all layer
  parameters stacked on a leading ``num_layers`` axis, the transformer
  body a single ``lax.scan`` — one compiled layer body, fast
  tracing/compilation;
- attention/MLP matmuls stay [B*T, D] x [D, F] shaped so XLA tiles them
  onto the MXU; params and activations are bfloat16, RMSNorm/softmax/rope
  accumulate in float32;
- serving (``*_layers_paged``, reached through models/registry.py):
  per-layer weight buffers, unrolled layers, K/V in a shared page pool
  per layer that the engine's jit donates, so XLA updates it in place;
  read by the ragged Pallas kernel (ops/page_attention.py) or the XLA
  gather behind the same interface;
- ``decode_layers``, ``extend_layers``, ``_chunk_layers``,
  ``init_kv_cache_layers`` and ``draft_propose_layers`` are the resident
  DRAFT model's private cache walks (engine/spec_draft.py): dense
  per-slot strips, one set per layer, no page tables.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from generativeaiexamples_tpu.ops import flash_attention, int8_matmul, page_attention

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    """Architecture hyperparameters (Llama-3 defaults)."""

    vocab_size: int = 128256
    hidden_size: int = 4096
    intermediate_size: int = 14336
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: int = 128
    rope_theta: float = 500_000.0
    norm_eps: float = 1e-5
    max_seq_len: int = 8192
    tie_embeddings: bool = False

    @property
    def q_dim(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.head_dim


# Named presets; selected via EngineConfig.model_config_name.
PRESETS: Dict[str, LlamaConfig] = {
    "llama3-8b": LlamaConfig(),
    "llama3-70b": LlamaConfig(
        hidden_size=8192,
        intermediate_size=28672,
        num_layers=80,
        num_heads=64,
        num_kv_heads=8,
    ),
    "llama3-1b-proxy": LlamaConfig(
        hidden_size=2048,
        intermediate_size=5504,
        num_layers=16,
        num_heads=16,
        num_kv_heads=8,
        head_dim=128,
    ),
    # Tiny configs for tests and the virtual-device dry run.
    # llama3-70b-tiny keeps the flagship's TOPOLOGY (80 layers, 64 query /
    # 8 KV heads — the shapes that drive TP sharding rules on v5e-8) at
    # dims small enough to compile+run on a virtual CPU mesh.
    "llama3-70b-tiny": LlamaConfig(
        vocab_size=512,
        hidden_size=256,
        intermediate_size=512,
        num_layers=80,
        num_heads=64,
        num_kv_heads=8,
        head_dim=4,
        max_seq_len=128,
    ),
    "debug": LlamaConfig(
        vocab_size=512,
        hidden_size=64,
        intermediate_size=128,
        num_layers=2,
        num_heads=4,
        num_kv_heads=2,
        head_dim=16,
        max_seq_len=128,
    ),
    # debug dims with a real context window: multi-turn prompts (chain
    # preamble + growing history, ~650 byte-tokenizer ids by turn 4)
    # must fit UNTRUNCATED for prefix-reuse structure to exist at all —
    # the fleet bench's placement A/B (tools/loadgen/fleet.py) measures
    # exactly that structure, and debug's 128-token window tail-cuts it.
    "debug-1k": LlamaConfig(
        vocab_size=512,
        hidden_size=64,
        intermediate_size=128,
        num_layers=2,
        num_heads=4,
        num_kv_heads=2,
        head_dim=16,
        max_seq_len=1024,
    ),
    # Tiny resident-draft config for speculative decoding tests: same
    # vocab/window as "debug" (proposals must be target-vocab ids) at a
    # fraction of its compute — a draft that is genuinely SMALLER than
    # its target, so acceptance reflects real draft/target disagreement
    # (pairing "debug" with itself instead gives the shared-weights
    # ~1.0-acceptance calibration ceiling bench's provenance flags).
    "debug-draft": LlamaConfig(
        vocab_size=512,
        hidden_size=32,
        intermediate_size=64,
        num_layers=1,
        num_heads=2,
        num_kv_heads=1,
        head_dim=16,
        max_seq_len=128,
    ),
    "debug-8dev": LlamaConfig(
        vocab_size=512,
        hidden_size=128,
        intermediate_size=256,
        num_layers=2,
        num_heads=8,
        num_kv_heads=8,
        head_dim=16,
        max_seq_len=128,
    ),
    # ONE SHARD of llama3-70b at TP=8, at full dims: every tensor has
    # exactly the per-chip shape of the v5e-8 deployment (hidden stays
    # 8192 — it is never sharded; heads, MLP width, and vocab divide by
    # 8). Serving THIS on one real 16 GB chip measures the 70B fit plan's
    # actual allocator behavior (~91% HBM: ~8.6 GB int8 weights + 5.5 GB
    # int8 KV at bs=32 S=8192) instead of asserting it by arithmetic —
    # and its decode step time bounds the real TP=8 per-step time from
    # below (missing only the psum/collective cost). BASELINE.md §70B.
    "llama3-70b-shard8": LlamaConfig(
        vocab_size=16032,
        hidden_size=8192,
        intermediate_size=3584,
        num_layers=80,
        num_heads=8,
        num_kv_heads=1,
        head_dim=128,
        max_seq_len=8192,
    ),
    # Kernel-compatible tiny config for the TP shard_map kernel tests:
    # head_dim=128 (lane-sized) and 64Q/8KV heads so an 8-way shard
    # keeps 8 local query heads — the geometry all three Pallas kernels
    # accept, at dims a virtual CPU mesh can run in interpret mode.
    "kernel-8dev": LlamaConfig(
        vocab_size=512,
        hidden_size=256,
        intermediate_size=512,
        num_layers=2,
        num_heads=64,
        num_kv_heads=8,
        head_dim=128,
        max_seq_len=256,
    ),
}


def init_spec(cfg: LlamaConfig) -> Dict[str, Tuple[Tuple[int, ...], float]]:
    """Single source of truth for random-init: weight name -> (shape, std).

    Consumed by init_params (jax PRNG), init_params_fast (numpy PRNG),
    and ops/quant.init_packed_params_int8 (direct int8) so the three
    initializers cannot drift. Norm weights (ones) are not listed.
    """
    h, q, kv, f, L = cfg.hidden_size, cfg.q_dim, cfg.kv_dim, cfg.intermediate_size, cfg.num_layers
    inv_h = 1.0 / math.sqrt(h)
    spec = {
        "embed": ((cfg.vocab_size, h), inv_h),
        "wq": ((L, h, q), inv_h),
        "wk": ((L, h, kv), inv_h),
        "wv": ((L, h, kv), inv_h),
        "wo": ((L, q, h), 1.0 / math.sqrt(q) / math.sqrt(2 * L)),
        "w_gate": ((L, h, f), inv_h),
        "w_up": ((L, h, f), inv_h),
        "w_down": ((L, f, h), 1.0 / math.sqrt(f) / math.sqrt(2 * L)),
    }
    if not cfg.tie_embeddings:
        spec["lm_head"] = ((h, cfg.vocab_size), inv_h)
    return spec


def _assemble_params(cfg: LlamaConfig, normal, dtype) -> Params:
    """Build the param pytree from a ``normal(name) -> array`` sampler —
    the single assembly site shared by both initializers."""
    L, h = cfg.num_layers, cfg.hidden_size
    params: Params = {
        "embed": normal("embed"),
        "layers": {
            "attn_norm": jnp.ones((L, h), dtype),
            "wq": normal("wq"),
            "wk": normal("wk"),
            "wv": normal("wv"),
            "wo": normal("wo"),
            "mlp_norm": jnp.ones((L, h), dtype),
            "w_gate": normal("w_gate"),
            "w_up": normal("w_up"),
            "w_down": normal("w_down"),
        },
        "final_norm": jnp.ones((h,), dtype),
    }
    if "lm_head" in init_spec(cfg):
        params["lm_head"] = normal("lm_head")
    return params


def init_params(
    cfg: LlamaConfig, key: jax.Array, dtype: jnp.dtype = jnp.bfloat16
) -> Params:
    """Deterministic scaled-normal init; layer params stacked on axis 0."""
    spec = init_spec(cfg)
    keys = dict(zip(sorted(spec), jax.random.split(key, len(spec))))

    def normal(name):
        shape, scale = spec[name]
        return (jax.random.normal(keys[name], shape, jnp.float32) * scale).astype(dtype)

    return _assemble_params(cfg, normal, dtype)


def init_params_fast(
    cfg: LlamaConfig, seed: int = 0, dtype: jnp.dtype = jnp.bfloat16
) -> Params:
    """Numpy-RNG twin of init_params for host staging of big models.

    jax's threefry on the single-core CPU backend needs minutes for 8B+
    random weights; the serving engine's no-checkpoint path (proxy
    benchmarks) only needs *plausible* weights, so PCG64 at ~10x the
    speed is the right trade. Same pytree structure and scale factors.
    """
    import numpy as np

    rng = np.random.default_rng(seed)
    spec = init_spec(cfg)

    def normal(name):
        shape, scale = spec[name]
        w = rng.standard_normal(size=shape, dtype=np.float32) * np.float32(scale)
        return jnp.asarray(w.astype(jnp.dtype(dtype)))

    return _assemble_params(cfg, normal, dtype)


def rms_norm(x: jax.Array, weight: jax.Array, eps: float) -> jax.Array:
    x32 = x.astype(jnp.float32)
    rms = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * rms).astype(x.dtype) * weight


def _rope_freqs(cfg: LlamaConfig) -> jax.Array:
    half = cfg.head_dim // 2
    return cfg.rope_theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)


def apply_rope(x: jax.Array, positions: jax.Array, cfg: LlamaConfig) -> jax.Array:
    """Rotary embedding. x: [B, T, H, Dh], positions: [B, T] int32."""
    freqs = _rope_freqs(cfg)  # [Dh/2]
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B, T, Dh/2]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x32 = x.astype(jnp.float32)
    x1, x2 = jnp.split(x32, 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def _attention(
    q: jax.Array,  # [B, T, Hq, Dh]
    k: jax.Array,  # [B, S, Hkv, Dh]
    v: jax.Array,  # [B, S, Hkv, Dh]
    mask: jax.Array,  # [B, T, S] bool, True = attend
) -> jax.Array:
    """Grouped-query attention via einsum; fp32 softmax accumulation.

    The XLA path; the Pallas flash kernel (ops/pallas_attention.py) replaces
    this on TPU for long sequences.
    """
    B, T, Hq, Dh = q.shape
    Hkv = k.shape[2]
    group = Hq // Hkv
    q = q.reshape(B, T, Hkv, group, Dh)
    scores = jnp.einsum("btkgd,bskd->bkgts", q, k, preferred_element_type=jnp.float32)
    scores = scores / math.sqrt(Dh)
    scores = jnp.where(mask[:, None, None, :, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgts,bskd->btkgd", probs.astype(v.dtype), v)
    return out.reshape(B, T, Hq, Dh)


def _proj(
    x: jax.Array, w, lora, name: str, scale: float, quant_kernel=None, tp=None
) -> jax.Array:
    """x @ w, plus the low-rank LoRA delta ``scale * (x @ A) @ B`` when the
    per-layer ``lora`` dict carries adapters for this projection.

    ``w`` is either a dense [K, F] matrix or an int8 pack
    {"q", "scale"} from ops/quant.py, served via the Pallas
    weight-streaming kernel (ops/int8_matmul.py); ``quant_kernel``
    forwards the caller's kernel-vs-XLA choice. ``tp`` (a
    parallel/tp_kernels.TPContext) routes packs through the shard_map
    kernel path on tensor-parallel meshes — the pack layout is then
    per-shard (ops/quant.py tp_shards) and MUST NOT hit the
    global-slicing paths."""
    if isinstance(w, dict):
        if tp is not None:
            from generativeaiexamples_tpu.parallel import tp_kernels
            from generativeaiexamples_tpu.ops.quant import PACK_KINDS

            # 'w8a8_xla' never reaches here: the engine only selects it
            # when no TP context exists (llm_engine._quant_kernel).
            out = tp_kernels.packed_matmul_tp(
                x, w, tp, PACK_KINDS[name], w8a8=(quant_kernel == "w8a8")
            )
        else:
            out = int8_matmul.packed_matmul(x, w, use_pallas=quant_kernel)
    else:
        out = x @ w
    if lora is not None and f"{name}_a" in lora:
        delta = (x @ lora[f"{name}_a"]) @ lora[f"{name}_b"]
        out = out + (scale * delta).astype(out.dtype)
    return out


def _lora_delta(x, lora, name: str, scale: float):
    """Standalone LoRA delta for projections folded into a fused matmul."""
    if lora is None or f"{name}_a" not in lora:
        return None
    return (scale * ((x @ lora[f"{name}_a"]) @ lora[f"{name}_b"])).astype(x.dtype)


def _block(
    h, lp, cfg: LlamaConfig, positions, attn,
    lora=None, lora_scale: float = 1.0, quant_kernel=None, tp=None,
):
    """One transformer block shared by forward and prefill.

    ``attn(q, k, v) -> (attn_out, aux)`` supplies the attention flavor
    (einsum over cache, plain causal, or the Pallas flash kernel) plus
    whatever per-layer state the caller scans out (updated cache / fresh
    K,V). ``lora`` optionally carries this layer's low-rank adapters
    (models/lora.py) — used in fine-tuning; serving merges them instead.
    """
    B, T = h.shape[:2]
    x = rms_norm(h, lp["attn_norm"], cfg.norm_eps)
    if "wqkv" in lp:
        # int8-fused serving path (ops/quant.py): one packed matmul for
        # Q|K|V, one for gate|up — fewer kernel dispatches per layer.
        # Per-projection LoRA deltas still apply, on the output slices.
        qkv = _proj(x, lp["wqkv"], None, "wqkv", lora_scale, quant_kernel, tp)
        q, k, v = jnp.split(qkv, [cfg.q_dim, cfg.q_dim + cfg.kv_dim], axis=-1)
        for name, ref in (("wq", "q"), ("wk", "k"), ("wv", "v")):
            delta = _lora_delta(x, lora, name, lora_scale)
            if delta is not None:
                if ref == "q":
                    q = q + delta
                elif ref == "k":
                    k = k + delta
                else:
                    v = v + delta
    else:
        q = _proj(x, lp["wq"], lora, "wq", lora_scale, quant_kernel, tp)
        k = _proj(x, lp["wk"], lora, "wk", lora_scale, quant_kernel, tp)
        v = _proj(x, lp["wv"], lora, "wv", lora_scale, quant_kernel, tp)
    q = q.reshape(B, T, cfg.num_heads, cfg.head_dim)
    k = k.reshape(B, T, cfg.num_kv_heads, cfg.head_dim)
    v = v.reshape(B, T, cfg.num_kv_heads, cfg.head_dim)
    q = apply_rope(q, positions, cfg)
    k = apply_rope(k, positions, cfg)
    attn_out, aux = attn(q, k, v)
    h = h + _proj(
        attn_out.reshape(B, T, cfg.q_dim), lp["wo"], lora, "wo", lora_scale,
        quant_kernel, tp,
    )
    x = rms_norm(h, lp["mlp_norm"], cfg.norm_eps)
    if "w_gateup" in lp:
        gateup = _proj(x, lp["w_gateup"], None, "w_gateup", lora_scale, quant_kernel, tp)
        gate_raw, up = jnp.split(gateup, [cfg.intermediate_size], axis=-1)
        dg = _lora_delta(x, lora, "w_gate", lora_scale)
        du = _lora_delta(x, lora, "w_up", lora_scale)
        gate_raw = gate_raw if dg is None else gate_raw + dg
        up = up if du is None else up + du
    else:
        gate_raw = _proj(x, lp["w_gate"], lora, "w_gate", lora_scale, quant_kernel, tp)
        up = _proj(x, lp["w_up"], lora, "w_up", lora_scale, quant_kernel, tp)
    gate = jax.nn.silu(gate_raw.astype(jnp.float32)).astype(x.dtype)
    h = h + _proj(gate * up, lp["w_down"], lora, "w_down", lora_scale, quant_kernel, tp)
    return h, aux


def _head(
    params: Params, h: jax.Array, cfg: LlamaConfig, quant_kernel=None, tp=None
) -> jax.Array:
    """Final RMSNorm + (possibly tied) lm head; fp32 logits."""
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    head = params.get("lm_head")
    if head is None:
        head = params["embed"].T
    if isinstance(head, dict):  # int8-packed (ops/quant.py)
        if tp is not None:
            from generativeaiexamples_tpu.parallel import tp_kernels

            return tp_kernels.packed_matmul_tp(
                h, head, tp, "column", w8a8=(quant_kernel == "w8a8")
            ).astype(jnp.float32)
        return int8_matmul.packed_matmul(h, head, use_pallas=quant_kernel).astype(
            jnp.float32
        )
    return (h @ head).astype(jnp.float32)


def forward(
    params: Params,
    cfg: LlamaConfig,
    tokens: jax.Array,  # [B, T] int32
    positions: jax.Array,  # [B, T] int32 absolute positions
    remat: bool = False,
    lora: Optional[Params] = None,
    lora_scale: float = 1.0,
    quant_kernel: Optional[bool] = None,
) -> Tuple[jax.Array, None]:
    """Run the decoder cache-free: plain causal attention over T
    (training, compile checks, the tests' reference for the serving
    walks). Returns (logits [B, T, V], None)."""
    h = params["embed"][tokens]  # gather: [B, T, D]

    mask = positions[:, :, None] >= positions[:, None, :]

    def layer(h, xs):
        def attn(q, k, v):
            return _attention(q, k, v, mask), ()

        return _block(
            h, xs["params"], cfg, positions, attn,
            lora=xs.get("lora"), lora_scale=lora_scale,
            quant_kernel=quant_kernel,
        )

    xs = {"params": params["layers"]}
    if lora is not None:
        xs["lora"] = lora
    # Rematerialize each layer under grad: trade FLOPs for HBM so long
    # sequences fit (jax.checkpoint composes with the scan).
    body = jax.checkpoint(layer) if remat else layer
    h, _ = lax.scan(body, h, xs)
    return _head(params, h, cfg, quant_kernel), None


def count_params(params: Params) -> int:
    return sum(int(x.size) for x in jax.tree.leaves(params))


def count_logical_params(cfg: LlamaConfig) -> int:
    """Parameter count from the architecture alone (independent of
    storage: int8 packs pad K/F, so counting buffer elements over- and
    double-counts). Used for MFU math."""
    n = sum(math.prod(shape) for shape, _ in init_spec(cfg).values())
    n += cfg.num_layers * 2 * cfg.hidden_size + cfg.hidden_size  # RMSNorm weights
    return n


def serving_memory_bytes(
    cfg: LlamaConfig,
    batch: int,
    max_seq_len: int,
    weight_bytes: int = 1,  # int8 weight-only storage
    kv_bytes: float = 2,  # bf16 cache; 1 int8, 0.5 int4 (+scales below)
) -> Dict[str, int]:
    """Aggregate HBM the serving engine needs: weights + KV cache.

    The fit-planning arithmetic for the flagship topologies (the
    reference sizes these as GPU-memory requirements — 30 GB for 8B,
    320 GB multi-GPU for 70B, docs/support-matrix.md:35-46):
    llama3-70b int8 ≈ 69 GB weights ⇒ a v5e-8 slice (8 x 16 GB) needs
    TP=8 AND an int8 KV cache to leave working memory per chip.
    ``kv_bytes`` is per-element and may be fractional
    (utils/hardware.kv_bytes_per_element: int4 packs two values per
    byte); any quantized width (< 2) carries the f32 scale planes.
    """
    weights = count_logical_params(cfg) * weight_bytes
    kv = 2 * batch * max_seq_len * cfg.num_kv_heads * cfg.head_dim
    cache = int(kv * cfg.num_layers * kv_bytes)
    if kv_bytes < 2:  # quantized cache carries per-(token, head) f32 scales
        cache += 2 * batch * max_seq_len * cfg.num_kv_heads * cfg.num_layers * 4
    return {"weights": weights, "kv_cache": cache, "total": weights + cache}


# --------------------------------------------------------------------- //
# Per-layer (unrolled) walks.
#
# The scan-based forward above slices its stacked [L, ...] params per
# layer; when those slices feed Pallas calls (opaque to XLA fusion) the
# compiler materializes HBM copies first — measured ~20% of decode step
# time at B=32 for llama3-1b-proxy. Serving therefore stores weights and
# KV as per-layer pytrees and unrolls the layer loop: every Pallas
# operand is a whole buffer, no slicing anywhere. Training keeps the
# scan (compile time). ``prefill_layers`` is the cache-free prompt
# forward the paged prefill wraps; the walks over ``init_kv_cache_layers``
# strips below it are the DRAFT model's (see the module docstring).


def consume_split_params_layers(params: Params) -> Params:
    """Stacked param pytree -> per-layer-list layout (DESTRUCTIVE).

    Works on dense and int8-packed ("wqkv"/{"q","scale"}) trees alike,
    and on host numpy or device arrays (``v[i]`` slices where the array
    lives). The engine device_puts the STACKED tree first — a handful of
    large transfers instead of ~130 split leaves put one by one — then
    splits on device.

    CONSUMES the input: stacked leaves are popped out of the caller's
    ``params["layers"]`` dict as they are sliced, so (once the caller
    drops its own reference) device memory peaks at stacked + one leaf
    rather than 2x — the difference between fitting and OOMing an
    8B-class int8 tree on 16 GB HBM.
    """
    stacked = params["layers"]

    def leaf_count(tree):
        for v in tree.values():
            if isinstance(v, dict):
                return leaf_count(v)
            return v.shape[0]

    L = leaf_count(stacked)
    per_key: Dict[str, Any] = {}
    for key in list(stacked):
        val = stacked.pop(key)
        if isinstance(val, dict):
            per_key[key] = {
                k2: [v2[i] for i in range(L)] for k2, v2 in val.items()
            }
        else:
            per_key[key] = [val[i] for i in range(L)]
        del val  # free the stacked buffer before slicing the next one

    layers = []
    for i in range(L):
        lp: Dict[str, Any] = {}
        for key, v in per_key.items():
            if isinstance(v, dict):
                lp[key] = {k2: lists[i] for k2, lists in v.items()}
            else:
                lp[key] = v[i]
        layers.append(lp)
    out = {k: v for k, v in params.items() if k != "layers"}
    out["layers"] = layers
    return out


def init_kv_cache_layers(
    cfg: LlamaConfig,
    batch: int,
    max_seq_len: Optional[int] = None,
    dtype: jnp.dtype = jnp.bfloat16,
    quantized: bool = False,
) -> list:
    """Dense per-slot KV strips, one set per layer (the draft model's
    private cache): bf16 [B, S, Hkv, Dh]; quantized head-major
    [B, Hkv, S, Dh] int8 with per-token per-head scales [B, Hkv, 1, S],
    the layout ``ops/decode_attention.decode_attention_xla`` reads."""
    S = max_seq_len or cfg.max_seq_len
    B, Hkv, Dh = batch, cfg.num_kv_heads, cfg.head_dim

    def one():
        if quantized:
            return {
                "k": jnp.zeros((B, Hkv, S, Dh), jnp.int8),
                "v": jnp.zeros((B, Hkv, S, Dh), jnp.int8),
                "ks": jnp.zeros((B, Hkv, 1, S), jnp.float32),
                "vs": jnp.zeros((B, Hkv, 1, S), jnp.float32),
            }
        return {
            "k": jnp.zeros((B, S, Hkv, Dh), dtype),
            "v": jnp.zeros((B, S, Hkv, Dh), dtype),
        }

    return [one() for _ in range(cfg.num_layers)]


def quantize_kv(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Symmetric per-(token, head) absmax int8 rows: [..., Dh] ->
    (int8 [..., Dh], f32 scale [...])."""
    x32 = x.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(x32), axis=-1) / 127.0, 1e-8)
    q = jnp.clip(jnp.round(x32 / s[..., None]), -127, 127).astype(jnp.int8)
    return q, s


def quantize_kv_int4(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Symmetric per-(token, head) absmax int4 rows, packed two per
    byte: [..., Dh] -> (uint8 [..., Dh//2], f32 scale [...]).

    Split-halves codec (NOT interleaved): the low nibble of byte ``i``
    holds lane ``i``, the high nibble lane ``i + Dh/2`` — unpacking is a
    nibble extract + lane-axis concat, no cross-lane shuffle (the
    Mosaic-friendly layout ops/page_attention._unpack_nibbles mirrors).
    Values clip to [-7, 7] (symmetric; -8 is never written) so the
    dequant ``q * scale`` is exact through bf16, preserving the
    exact-operand kernel discipline the int8 path pins.
    """
    dh = x.shape[-1]
    assert dh % 2 == 0, dh
    x32 = x.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(x32), axis=-1) / 7.0, 1e-8)
    q = jnp.clip(jnp.round(x32 / s[..., None]), -7, 7).astype(jnp.int32)
    lo = q[..., : dh // 2] & 0xF
    hi = q[..., dh // 2:] & 0xF
    return (lo | (hi << 4)).astype(jnp.uint8), s


def unpack_int4(packed: jax.Array) -> jax.Array:
    """Inverse of :func:`quantize_kv_int4`'s packing: uint8
    [..., Dh//2] -> int8 [..., Dh] integer values in [-8, 7] (dequant is
    the caller's ``astype(f32) * scale``, same formula as int8)."""
    w = packed.astype(jnp.int32)
    lo = w & 0xF
    hi = (w >> 4) & 0xF
    lo = jnp.where(lo >= 8, lo - 16, lo)
    hi = jnp.where(hi >= 8, hi - 16, hi)
    return jnp.concatenate([lo, hi], axis=-1).astype(jnp.int8)


def prefill_layers(
    params: Params,
    cfg: LlamaConfig,
    tokens: jax.Array,  # [B, T] right-padded prompts
    lengths: jax.Array,  # [B]
    use_flash: Optional[bool] = None,
    interpret: bool = False,
    quant_kernel: Optional[bool] = None,
    tp=None,
) -> Tuple[jax.Array, list]:
    """Unrolled prefill; returns (last-token logits [B, V], per-layer
    (k, v) [B, T, Hkv, Dh] for the caller to write into its cache). A
    fresh sequence's cache is empty, so prefill attends causally over
    just the T prompt tokens (the Pallas flash kernel when shapes allow)
    and the lm_head runs on the single last-token hidden state.
    Right-padding rows are garbage but never read (logits are taken at
    ``lengths - 1``) and overwritten by decode before the causal mask
    ever exposes them. With ``tp``
    (parallel/tp_kernels.TPContext) the flash kernel runs head-sharded
    via shard_map and packed matmuls on per-shard tiles."""
    B, T = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    if use_flash is None:
        use_flash = flash_attention.preferred(T, cfg.head_dim)
    if use_flash and tp is not None:
        from generativeaiexamples_tpu.parallel import tp_kernels

        use_flash = tp_kernels.flash_supported(cfg, tp.shards, T)
    h = params["embed"][tokens]
    mask = None if use_flash else positions[:, :, None] >= positions[:, None, :]
    kvs = []
    for lp in params["layers"]:
        def attn(q, k, v):
            kvs.append((k, v))
            if use_flash and tp is not None:
                from generativeaiexamples_tpu.parallel import tp_kernels

                out = tp_kernels.flash_attention_tp(q, k, v, tp)
            elif use_flash:
                out = flash_attention.flash_attention_causal(
                    q, k, v, interpret=interpret
                )
            else:
                out = _attention(q, k, v, mask)
            return out, ()

        h, _ = _block(h, lp, cfg, positions, attn, quant_kernel=quant_kernel, tp=tp)

    last_h = jnp.take_along_axis(h, (lengths - 1)[:, None, None], axis=1)
    last = _head(params, last_h, cfg, quant_kernel, tp=tp)[:, 0, :]
    return last, kvs


def extend_layers(
    params: Params,
    cfg: LlamaConfig,
    tokens: jax.Array,  # [N, C] — one prompt CHUNK per admitted row
    offsets: jax.Array,  # [N] absolute position of each row's chunk start
    valid: jax.Array,  # [N] real tokens in this chunk (0..C; 0 = done row)
    slots: jax.Array,  # [N] target cache slots
    caches: list,
    window: int,  # static: power-of-two >= max(offsets) + C
    quant_kernel: Optional[bool] = None,
    tp=None,
) -> Tuple[jax.Array, list]:
    """CHUNKED prefill over per-layer slot caches; returns (last-valid
    hidden states [N, D], updated caches).

    The bucket-miss fix (VERDICT r3 #4): a prompt of ANY length is
    prefilled as ceil(T/C) dispatches of this one executable family —
    shapes depend only on (N, C, window), all warmed at startup — so no
    prompt length can trigger an XLA compile inside a request (the
    monolithic prefill compiled one executable per length bucket;
    observed p95 254 s when retrieval crossed a cold bucket, and >15 min
    for one 70B bucket). Chunk k of a wave attends its C queries against
    the slot cache prefix [:window] — rows < offset were written by
    chunks 0..k-1 — plus within-chunk causality, then scatters its K/V
    rows at [slot, offset:offset+C].

    Rows whose prompt ends before this chunk (``valid == 0``) and the
    garbage tail of a final partial chunk are handled by value-masking:
    cache writes gather the current rows and select per-token, so a
    masked write is a no-op by value. The returned hidden state per row
    is at ``clip(valid, 1, C) - 1`` — the row's true last prompt token
    exactly when this is its final chunk; the engine keeps, per row, the
    last candidate with ``valid > 0`` (models the reference's TRT-LLM
    chunked-context mode, docs/architecture.md:54-66).

    int8-KV numerics note: each chunk's queries attend the DEQUANTIZED
    cache rows (including the chunk's own rows, quantized on write), so
    prefill logits differ from the monolithic path — which attends
    full-precision fresh K/V — by quantization error. Chunk-size choices
    do NOT change the numbers (per-row quantization is independent of
    chunking), so any two chunkings of the same prompt match exactly.
    """
    C = tokens.shape[1]
    h, new_caches = _chunk_layers(
        params, cfg, tokens, offsets, valid, slots, caches, window,
        quant_kernel=quant_kernel, tp=tp,
    )
    last_idx = jnp.clip(valid, 1, C) - 1
    last_h = jnp.take_along_axis(h, last_idx[:, None, None], axis=1)[:, 0]  # [N, D]
    return last_h, new_caches


def _chunk_layers(
    params: Params,
    cfg: LlamaConfig,
    tokens: jax.Array,  # [N, C]
    offsets: jax.Array,  # [N]
    valid: jax.Array,  # [N]
    slots: jax.Array,  # [N]
    caches: list,
    window: int,
    quant_kernel: Optional[bool] = None,
    tp=None,
) -> Tuple[jax.Array, list]:
    """Chunk body of ``extend_layers`` and ``draft_propose_layers``: write
    the chunk's K/V rows at [slot, offset:offset+C] (value-masked by
    ``valid``), attend the [:window] cache prefix + within-chunk causal,
    and return (hidden states [N, C, D], updated caches)."""
    N, C = tokens.shape
    quantized = "ks" in caches[0]
    S = caches[0]["k"].shape[2] if quantized else caches[0]["k"].shape[1]
    W = min(window, S)
    Hkv = cfg.num_kv_heads
    positions = offsets[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]  # [N, C]
    # clamp garbage-tail positions into the cache; their writes are
    # value-masked and their queries' outputs discarded
    positions = jnp.minimum(positions, S - 1)
    tok_valid = jnp.arange(C, dtype=jnp.int32)[None, :] < valid[:, None]  # [N, C]
    h = params["embed"][tokens]
    kv_pos = jnp.arange(W, dtype=jnp.int32)
    # query at absolute position p sees cache rows <= p (earlier chunks
    # of the same request + within-chunk causal)
    mask = kv_pos[None, None, :] <= positions[:, :, None]  # [N, C, W]
    s1 = slots[:, None]  # [N, 1]
    head_idx = jnp.arange(Hkv, dtype=jnp.int32)
    new_caches = []
    for lp, c in zip(params["layers"], caches):
        def attn(q, k, v, c=c):
            if quantized:
                kq, ksn = quantize_kv(k)  # [N,C,Hkv,Dh], [N,C,Hkv]
                vq, vsn = quantize_kv(v)
                s3 = slots[:, None, None]  # [N,1,1]
                h3 = head_idx[None, :, None]  # [1,Hkv,1]
                p3 = positions[:, None, :]  # [N,1,C]
                z3 = jnp.zeros_like(p3)
                m3 = tok_valid[:, None, :]  # [N,1,C]
                cur_k = c["k"][s3, h3, p3]  # [N,Hkv,C,Dh]
                cur_v = c["v"][s3, h3, p3]
                cur_ks = c["ks"][s3, h3, z3, p3]  # [N,Hkv,C]
                cur_vs = c["vs"][s3, h3, z3, p3]
                row_k = jnp.where(m3[..., None], jnp.swapaxes(kq, 1, 2), cur_k)
                row_v = jnp.where(m3[..., None], jnp.swapaxes(vq, 1, 2), cur_v)
                row_ks = jnp.where(m3, jnp.swapaxes(ksn, 1, 2), cur_ks)
                row_vs = jnp.where(m3, jnp.swapaxes(vsn, 1, 2), cur_vs)
                ck = c["k"].at[s3, h3, p3].set(row_k)
                cv = c["v"].at[s3, h3, p3].set(row_v)
                cks = c["ks"].at[s3, h3, z3, p3].set(row_ks)
                cvs = c["vs"].at[s3, h3, z3, p3].set(row_vs)
                new_caches.append({"k": ck, "v": cv, "ks": cks, "vs": cvs})
                # dequant gather of the attention window for this wave's
                # slots (the multi-query analogue of decode_attention_xla):
                # [N, Hkv, W, Dh] int8 rows x [N, Hkv, W] scales
                kw = (ck[slots][:, :, :W].astype(jnp.float32)
                      * cks[slots][:, :, 0, :W][..., None])
                vw = (cv[slots][:, :, :W].astype(jnp.float32)
                      * cvs[slots][:, :, 0, :W][..., None])
                kw = jnp.swapaxes(kw, 1, 2).astype(q.dtype)  # [N,W,Hkv,Dh]
                vw = jnp.swapaxes(vw, 1, 2).astype(q.dtype)
                out = _attention(q, kw, vw, mask)
            else:
                cur_k = c["k"][s1, positions]  # [N,C,Hkv,Dh]
                cur_v = c["v"][s1, positions]
                row_k = jnp.where(
                    tok_valid[..., None, None], k.astype(c["k"].dtype), cur_k
                )
                row_v = jnp.where(
                    tok_valid[..., None, None], v.astype(c["v"].dtype), cur_v
                )
                ck = c["k"].at[s1, positions].set(row_k)
                cv = c["v"].at[s1, positions].set(row_v)
                new_caches.append({"k": ck, "v": cv})
                out = _attention(q, ck[slots][:, :W], cv[slots][:, :W], mask)
            return out, ()

        h, _ = _block(h, lp, cfg, positions, attn, quant_kernel=quant_kernel, tp=tp)

    return h, new_caches


def draft_propose_layers(
    params: Params,
    cfg: LlamaConfig,
    tokens: jax.Array,  # [B, C0] catch-up chunk (tokens past each row's frontier)
    offsets: jax.Array,  # [B] each row's draft-KV frontier (absolute position)
    valid: jax.Array,  # [B] catch-up tokens in this chunk (0 = dead row)
    caches: list,  # the DRAFT model's per-layer strips (init_kv_cache_layers)
    window: int,  # static: power-of-two covering frontier + C0 + draft_k
    draft_k: int,  # static: proposal width K (spec_decode.effective_draft_len)
    vocab: int,  # static: argmax slice — the TARGET's sampling vocab
    quant_kernel: Optional[bool] = None,
    tp=None,
) -> Tuple[jax.Array, list]:
    """Fused resident-draft proposal: catch-up + K greedy draft steps in
    ONE compiled dispatch for the whole decode wave (docs/spec_decode.md).

    1. **Catch-up**: the tokens the target emitted since each row's
       draft frontier (at most ``draft_k + 1`` — the previous round's
       accepted prefix plus the bonus token) run as one
       ``_chunk_layers`` pass over the draft caches, writing their K/V
       rows at ``[offset, offset + valid)`` and producing the logits
       after the row's full context. This overwrite IS the acceptance
       rewind: the previous round's rejected speculative rows sit in
       exactly that span (or above the new frontier, where the
       position mask hides them until a later catch-up overwrites them
       too) — the same rejected-row rule the target's verify chunk
       relies on.
    2. **Draft**: the catch-up logits' argmax is draft token 1; a
       ``lax.scan`` of ``draft_k - 1`` single-token ``decode_layers``
       steps (speculative K/V rows written above the frontier) drafts
       the rest.

    Returns ``([B, draft_k] int32 proposals, updated caches)``. Dead
    rows (``valid == 0``) write nothing in the catch-up; their scan
    writes land at row 0 of their own slot's strip, which only matters
    for a slot whose draft state is already dead (admission re-prefills
    it from position 0). ``vocab`` bounds the argmax to the target's
    sampling vocab so every proposal is a token the verify program
    could emit.
    """
    B, C0 = tokens.shape
    quantized = "ks" in caches[0]
    S = caches[0]["k"].shape[2] if quantized else caches[0]["k"].shape[1]
    slot_ids = jnp.arange(B, dtype=jnp.int32)
    h, caches = _chunk_layers(
        params, cfg, tokens, offsets, valid, slot_ids, caches, window,
        quant_kernel=quant_kernel, tp=tp,
    )
    last_idx = jnp.clip(valid, 1, C0) - 1
    last_h = jnp.take_along_axis(h, last_idx[:, None, None], axis=1)
    logits = _head(params, last_h, cfg, quant_kernel, tp=tp)[:, 0, :]
    live = valid > 0
    first = jnp.argmax(logits[:, :vocab], axis=-1).astype(jnp.int32)
    # the first draft token's K/V row lands right past the caught-up
    # frontier; dead rows park at position 0 of their own strip
    pos = jnp.where(live, jnp.minimum(offsets + jnp.maximum(valid, 1), S - 1), 0)
    if draft_k <= 1:
        return first[:, None], caches

    def body(carry, _):
        tok, p, caches = carry
        lg, caches = decode_layers(
            params, cfg, tok, p, caches, window=window,
            quant_kernel=quant_kernel, tp=tp,
        )
        nt = jnp.argmax(lg[:, :vocab], axis=-1).astype(jnp.int32)
        np_ = jnp.where(live, jnp.minimum(p + 1, S - 1), 0)
        return (nt, np_, caches), nt

    (_, _, caches), rest = lax.scan(
        body, (first, pos, caches), None, length=draft_k - 1
    )  # rest: [K-1, B]
    drafts = jnp.concatenate([first[:, None], jnp.swapaxes(rest, 0, 1)], axis=1)
    return drafts, caches


def decode_layers(
    params: Params,
    cfg: LlamaConfig,
    tokens: jax.Array,  # [B]
    positions: jax.Array,  # [B]
    caches: list,
    window: Optional[int] = None,
    quant_kernel: Optional[bool] = None,
    tp=None,
) -> Tuple[jax.Array, list]:
    """One decode step over per-layer strips; returns (logits [B, V],
    updated caches). int8 caches attend through
    ``ops/decode_attention.decode_attention_xla``; bf16 caches use the
    einsum attention over a static ``window`` prefix (the caller
    guarantees every query position is < window, so the result is
    exact). With ``tp`` packed matmuls run on per-shard tiles."""
    from generativeaiexamples_tpu.ops import decode_attention as da

    B = tokens.shape[0]
    quantized = "ks" in caches[0]
    S = caches[0]["k"].shape[2] if quantized else caches[0]["k"].shape[1]
    W = min(window or S, S)
    h = params["embed"][tokens[:, None]]
    pos2 = positions[:, None]
    batch_idx = jnp.arange(B, dtype=jnp.int32)[:, None]
    if not quantized:
        mask = (
            jnp.arange(W, dtype=jnp.int32)[None, None, :] <= pos2[:, :, None]
        )
    head_idx = jnp.arange(cfg.num_kv_heads, dtype=jnp.int32)
    new_caches = []
    for lp, c in zip(params["layers"], caches):
        def attn(q, k, v, c=c):
            if quantized:
                kq, ksn = quantize_kv(k)  # [B,1,Hkv,Dh], [B,1,Hkv]
                vq, vsn = quantize_kv(v)
                b3 = batch_idx[:, :, None]  # [B,1,1]
                h3 = head_idx[None, None, :]  # [1,1,Hkv]
                p3 = pos2[:, :, None]  # [B,1,1]
                ck = c["k"].at[b3, h3, p3].set(kq)
                cv = c["v"].at[b3, h3, p3].set(vq)
                # all-advanced indices: a basic 0 between advanced ones
                # would trigger numpy's axis-reordering rule
                z3 = jnp.zeros_like(p3)
                cks = c["ks"].at[b3, h3, z3, p3].set(ksn)
                cvs = c["vs"].at[b3, h3, z3, p3].set(vsn)
                new_caches.append({"k": ck, "v": cv, "ks": cks, "vs": cvs})
                out = da.decode_attention_xla(
                    q, ck, cks, cv, cvs, pos2, window=W
                )
            else:
                ck = c["k"].at[batch_idx, pos2].set(k)
                cv = c["v"].at[batch_idx, pos2].set(v)
                new_caches.append({"k": ck, "v": cv})
                out = _attention(q, ck[:, :W], cv[:, :W], mask)
            return out, ()

        h, _ = _block(h, lp, cfg, pos2, attn, quant_kernel=quant_kernel, tp=tp)
    logits = _head(params, h, cfg, quant_kernel, tp=tp)
    return logits[:, 0, :], new_caches


# --------------------------------------------------------------------- //
# The serving KV cache: a page pool (docs/paged_kv.md).
#
# Instead of one dense [B, S, ...] strip per decode slot, K/V rows live
# in a shared page pool [P, page, Hkv, Dh]; a host-side allocator
# (engine/kv_pages.py) hands each request a page table — [Pmax] physical
# page ids — and the attention pass GATHERS the row's pages and masks to
# its live length. Page tables make prefix sharing zero-copy (a radix
# hit maps the shared pages, refcounted, into the new table) and let the
# admission planner fund mixed-length requests at page granularity.
#
# Exactness: the gathered window holds a row's first W tokens in
# order, and the attention over it is the plain math (einsum attention
# for bf16; ops/decode_attention.py's XLA dequant formula for int8), so
# greedy streams equal a cache-free ``forward`` decode token for token
# (tests/test_paged_kv.py, tests/test_one_serving_path.py).
#
# The attention READ has two servers behind one interface: the XLA
# gather below (every geometry; reads a bucketed W per row) and the
# ragged Pallas kernel in ops/page_attention.py (``page_kernel`` param;
# walks a scalar-prefetched work list of each row's live pages, so
# cache traffic and grid steps track true page-rounded lengths). The
# engine picks per executable through
# ``page_attention.supports_geometry`` and falls back loudly.
#
# Physical page 0 is the SCRATCH page: dead rows and value-masked
# garbage writes are pointed there (never at a stale table entry), so a
# released slot's in-flight dispatches can never scribble on pages the
# allocator has re-issued to a live request.


_LANES = 128  # the chip's lane width: a minor dimension below it pads to it


def kv_scale_plane_shape(
    page_size: int, num_kv_heads: int, head_sharded: bool = False
) -> Tuple[int, int]:
    """One page of a quantised pool's scale plane, as the pool stores it.

    The scales of a page are ``page * Hkv`` float32 in the flat order
    ``t * Hkv + h``: the column order of the page kernel's scores.
    LANE-DENSE ``[page * Hkv / 128, 128]`` cuts that order into 128-lane
    rows (element ``(r, l)`` is token ``(r * 128 + l) // Hkv``, head
    ``l % Hkv``): one 4 KB tile a page on the chip. TOKEN-MAJOR
    ``[page, Hkv]`` is the same bytes with ``Hkv`` as the minor
    dimension, which the chip pads to 128 lanes (64 KB moved for 4 KB a
    block, a relayout in the page read, a padded copy of every plane
    around every kernel dispatch: PERF.md §6, PR 43 and PR 45). It stays
    where the lanes cannot be had: a pool whose heads are sharded over a
    mesh (``parallel/sharding.kv_pool_specs`` shards that dimension) and
    a geometry whose tokens do not tile the lanes (``Hkv`` must divide
    128, so that a token's heads never straddle two rows, and ``page *
    Hkv`` be whole rows). This function alone decides; every reader
    tells the two apart by the plane's static shape."""
    cols = page_size * num_kv_heads
    if head_sharded or _LANES % num_kv_heads or cols % _LANES:
        return (page_size, num_kv_heads)
    return (cols // _LANES, _LANES)


class TokenRuns(NamedTuple):
    """Where a walk's tokens sit in the cache, run by run: run ``w`` is
    tokens ``starts[w] .. starts[w] + counts[w] - 1`` of the walk's
    flattened token axis, at CONSECUTIVE cache positions ``offsets[w]
    ..`` of the row whose page table is ``tables[w]``; ``seg`` (static)
    bounds a run's tokens. A packed wave's rows, a rectangle's rows and
    a decode step's single tokens are all runs. No two runs of a walk
    share a cache row (a slot rides a dispatch once)."""

    starts: Optional[jax.Array]  # [R] int32; None: run w is token w (one token a run)
    counts: jax.Array  # [R] int32 — live tokens (0: a dead run writes nothing)
    offsets: jax.Array  # [R] int32
    tables: jax.Array  # [R, Pmax] int32
    seg: int


def write_kv_scales(
    planes: Tuple[jax.Array, jax.Array], scales: Tuple[jax.Array, jax.Array],
    phys: jax.Array, sip: jax.Array, runs: TokenRuns,
) -> Tuple[jax.Array, jax.Array]:
    """A layer's two scale planes ``(ks, vs)`` with the walk's ``scales
    (ksn, vsn)``, each ``[..., Hkv]``, written, in either layout.

    TOKEN-MAJOR: one scatter a plane of ``[Hkv]`` rows at pool page
    ``phys [...]``, slot-in-page ``sip [...]`` (a dead token's on the
    scratch page, where the walk points it).

    LANE-DENSE: a token's scales are ``Hkv`` lanes of a 128-lane row
    that ``128 / Hkv`` consecutive tokens share, and the chip writes
    whole rows well and pieces of a row badly (a windowed scatter of
    ``[1, 1, Hkv]`` updates measured 3.9 us AN UPDATE, 16 ms a layer at
    2,048 tokens: PERF.md §6, PR 45). So the rows are rebuilt from the
    walk's ``runs`` (``_rebuild_scale_rows``), both planes in one pass;
    a dead token is not written at all."""
    if planes[0].shape[2] == scales[0].shape[-1]:  # token-major
        return tuple(p.at[phys, sip].set(s) for p, s in zip(planes, scales))
    return _rebuild_scale_rows(*planes, *scales, *runs)


@functools.partial(jax.jit, static_argnames=("seg",))
def _rebuild_scale_rows(ks, vs, ksn, vsn, starts, counts, offsets, tables, seg: int):
    """The lane-dense write. A run's tokens are consecutive, hence the
    128-lane row holding cache positions ``p .. p + 128 / Hkv - 1`` of
    run ``w`` is the 128 consecutive floats of the flattened scales from
    token ``starts[w] + p - offsets[w]`` on. Every row a run touches
    (``seg / (128 / Hkv) + 1`` at most) is cut out of the flattened
    scales (two aligned row reads, a select between them and a lane
    rotate in halving steps), takes the plane's old value on the lanes
    of tokens outside the run, and goes back as ONE row: a sixteenth of
    the updates at 8 heads, each a whole sublane. A run of one token (a
    decode step's) needs no cut: its row is the token's scales repeated
    along the lanes. Exact: selects only. What the op count buys: every
    small operation costs ~2 us on the chip and a program's load time
    grows with them, so K and V share the index arithmetic and the cut
    (PERF.md §6, PR 45). A jit of its own: a walk calls it once a layer
    on the same shapes, and is traced once."""
    hkv = ksn.shape[-1]
    rows_a_page = ks.shape[1]
    per_row = _LANES // hkv  # tokens a 128-lane row
    reach = (seg + per_row - 2) // per_row + 1  # rows a run of seg tokens can touch
    lane = jnp.arange(_LANES, dtype=jnp.int32)
    # [R, reach]: the run's rows by their index along its cache, and the
    # run-relative token each row begins with (negative: before the run)
    row = offsets[:, None] // per_row + jnp.arange(reach, dtype=jnp.int32)[None, :]
    first = row * per_row - offsets[:, None]
    token = first[..., None] + lane // hkv  # [R, reach, 128]
    ours = (token >= 0) & (token < counts[:, None, None])
    ours &= (row < tables.shape[1] * rows_a_page)[..., None]  # past the row's capacity: nobody's
    page = jnp.take_along_axis(
        tables, jnp.minimum(row // rows_a_page, tables.shape[1] - 1), axis=1
    )
    # a row no token of the run falls in rewrites the scratch page's row
    # with its own old value: every index stays in range (an index out
    # of range under mode="drop" once clobbered live updates on the
    # chip, PERF.md §6 PR 41), and equal values make the order moot
    page = jnp.where(ours.any(-1), page, 0)
    sub = row % rows_a_page
    if seg == 1:
        # one token a run: its row is its scales repeated along the lanes
        one = [x.reshape(-1, hkv) if starts is None else x.reshape(-1, hkv)[starts] for x in (ksn, vsn)]
        # (a concatenate, not a tile: the tile's reshape costs a relayout copy a plane a step)
        new = [jnp.concatenate([x] * per_row, axis=-1)[:, None, :] for x in one]  # [R, 1, 128] each
    else:
        # the 128 floats from token ``starts + first`` on, out of the
        # flattened scales padded by a row in front (first < 0) and two behind
        flat = jnp.stack([ksn.reshape(-1), vsn.reshape(-1)])
        flat = jnp.pad(flat, ((0, 0), (_LANES, 2 * _LANES + -flat.shape[1] % _LANES)))
        flat = flat.reshape(2, -1, _LANES)
        if starts is None:
            starts = jnp.arange(counts.shape[0], dtype=jnp.int32)
        at = jnp.clip((starts[:, None] + first) * hkv + _LANES, 0, (flat.shape[1] - 2) * _LANES)
        cut = (at % _LANES)[..., None]
        new = jnp.where(lane >= cut, flat[:, at // _LANES], flat[:, at // _LANES + 1])
        shift = hkv
        while shift < _LANES:  # rotate left by the cut, a multiple of hkv, bit by bit
            new = jnp.where((cut // shift) % 2 == 1, jnp.roll(new, -shift, axis=-1), new)
            shift *= 2
    return (
        ks.at[page, sub].set(jnp.where(ours, new[0], ks[page, sub])),
        vs.at[page, sub].set(jnp.where(ours, new[1], vs[page, sub])),
    )


def gather_kv_scales(
    plane: jax.Array, tables: jax.Array, pages_w: int, page_size: int
) -> jax.Array:
    """Each row's first ``pages_w`` pages of a scale plane as token rows
    ``[N, pages_w * page, Hkv]`` (the XLA gather paths' operand): both
    layouts hold a page's scales in the flat order ``t * Hkv + h``, so
    one reshape of the gathered pages reads either."""
    g = plane[tables[:, :pages_w]]  # [N, pages_w, <one page of the plane>]
    return g.reshape(g.shape[0], pages_w * page_size, -1)


def init_kv_pool(
    cfg: LlamaConfig,
    pool: int,
    page_size: int,
    dtype: jnp.dtype = jnp.bfloat16,
    quantized: bool = False,
    packed: bool = False,
    head_sharded: bool = False,
) -> list:
    """Per-layer page pools: [pool, page_size, Hkv, Dh] token-major. The
    int8 variant carries quantize_kv's per-(token, head) float32 scales
    in two planes ``ks`` / ``vs`` of ``[pool, *kv_scale_plane_shape]``:
    lane-dense ``[pool, page_size * Hkv / 128, 128]`` unless the pool's
    heads are to be sharded over a mesh (``head_sharded``, the engine's
    to say) or the geometry does not tile the lanes, then ``[pool,
    page_size, Hkv]``. Walks write and gather the planes through
    ``write_kv_scales`` / ``gather_kv_scales`` and the page kernel reads
    the layout off the operand's shape; none of them spells it.
    ``packed`` selects the int4 pool: uint8 [pool, page_size, Hkv,
    Dh//2] holding two values per byte (quantize_kv_int4's split-halves
    codec) with the same scale planes — readers detect it by the uint8
    dtype."""
    Hkv, Dh = cfg.num_kv_heads, cfg.head_dim
    if packed:
        assert Dh % 2 == 0, Dh
        rows, dtype = (pool, page_size, Hkv, Dh // 2), jnp.uint8
    else:
        rows, dtype = (pool, page_size, Hkv, Dh), jnp.int8 if quantized else dtype
    plane = (pool,) + kv_scale_plane_shape(page_size, Hkv, head_sharded)

    def one():
        layer = {"k": jnp.zeros(rows, dtype), "v": jnp.zeros(rows, dtype)}
        if packed or quantized:
            layer.update(ks=jnp.zeros(plane, jnp.float32), vs=jnp.zeros(plane, jnp.float32))
        return layer

    return [one() for _ in range(cfg.num_layers)]


def _gather_page_window(buf: jax.Array, tables: jax.Array, pages_w: int,
                        page_size: int) -> jax.Array:
    """Gather each row's first ``pages_w`` pages from the pool and
    flatten to token rows: buf [P, page, ...] x tables [N, Pmax] ->
    [N, pages_w * page, ...]. Unused table entries point at the scratch
    page; their rows are position-masked in the caller."""
    g = buf[tables[:, :pages_w]]  # [N, pages_w, page, ...]
    return g.reshape((g.shape[0], pages_w * page_size) + buf.shape[2:])


def write_prefill_pages(
    caches: list,
    kvs: list,  # per-layer (k, v) [N, T, Hkv, Dh] from prefill_layers
    row_tables: jax.Array,  # [N, Pmax] — the wave rows' page tables
    page_size: int,
) -> list:
    """Scatter a monolithic prefill wave's fresh K/V rows into the page
    pool through the wave rows' page tables. Garbage
    right-padding rows land in the rows' own reserved pages (overwritten
    by decode before any query attends them) or, past the reservation,
    on the scratch page."""
    N, T = kvs[0][0].shape[:2]
    quantized = "ks" in caches[0]
    packed = quantized and caches[0]["k"].dtype == jnp.uint8
    qfn = quantize_kv_int4 if packed else quantize_kv
    pos = jnp.arange(T, dtype=jnp.int32)[None, :]
    page_idx = jnp.broadcast_to(pos // page_size, (N, T))
    phys = jnp.take_along_axis(row_tables, page_idx, axis=1)  # [N, T]
    sip = jnp.broadcast_to(pos % page_size, (N, T))
    runs = TokenRuns(
        T * jnp.arange(N, dtype=jnp.int32), jnp.full((N,), T, jnp.int32),
        jnp.zeros((N,), jnp.int32), row_tables, T,
    )
    new_caches = []
    for c, (k, v) in zip(caches, kvs):
        if quantized:
            kq, ksn = qfn(k)  # [N,T,Hkv,Dh(/2)], [N,T,Hkv]
            vq, vsn = qfn(v)
            cks, cvs = write_kv_scales((c["ks"], c["vs"]), (ksn, vsn), phys, sip, runs)
            new_caches.append({
                "k": c["k"].at[phys, sip].set(kq),
                "v": c["v"].at[phys, sip].set(vq),
                "ks": cks, "vs": cvs,
            })
        else:
            new_caches.append({
                "k": c["k"].at[phys, sip].set(k.astype(c["k"].dtype)),
                "v": c["v"].at[phys, sip].set(v.astype(c["v"].dtype)),
            })
    return new_caches


def _paged_kernel_read(
    q, ck, cv, tables, positions, cks=None, cvs=None, *,
    interpret: bool, tp=None, work=None,
):
    """Route one ragged-kernel attention read: single-device pallas_call
    or, under a pure-TP mesh, the shard_map head-sharded variant
    (parallel/tp_kernels.paged_attention_tp). The engine only sets
    ``page_kernel`` with ``tp`` when ``supports_geometry(...,
    shards=tp.shards)`` accepted the LOCAL tile geometry. ``work`` is
    the step's ``page_attention.page_work_list``, built once by the
    caller and shared by every layer's read."""
    if tp is not None:
        from generativeaiexamples_tpu.parallel import tp_kernels

        return tp_kernels.paged_attention_tp(
            q, ck, cv, tables, positions, cks, cvs, tp=tp,
            interpret=interpret, work=work,
        )
    return page_attention.paged_attention(
        q, ck, cv, tables, positions, cks, cvs, interpret=interpret,
        work=work,
    )


def _window_attention(q, ck, cv, cks, cvs, tabs, positions, W: int,
                      page_size: int) -> jax.Array:
    """The gather read of a chunk: each row's first ``W`` cached tokens
    gathered from the pool through its page table ``tabs`` [n, Pmax]
    (an int8 / int4 pool dequantised by the plain formula: int -> f32,
    scale multiply, cast) into ``_attention`` under the causal mask of
    the queries' ``positions`` [n, C]. q: [n, C, Hq, Dh]."""
    Pw = W // page_size
    mask = jnp.arange(W, dtype=jnp.int32)[None, None, :] <= positions[:, :, None]
    gk = _gather_page_window(ck, tabs, Pw, page_size)
    gv = _gather_page_window(cv, tabs, Pw, page_size)
    if cks is not None:
        if ck.dtype == jnp.uint8:
            gk, gv = unpack_int4(gk), unpack_int4(gv)
        gk = (
            gk.astype(jnp.float32)
            * gather_kv_scales(cks, tabs, Pw, page_size)[..., None]
        ).astype(q.dtype)  # [n, W, Hkv, Dh]
        gv = (
            gv.astype(jnp.float32)
            * gather_kv_scales(cvs, tabs, Pw, page_size)[..., None]
        ).astype(q.dtype)
    return _attention(q, gk, gv, mask)


def _chunk_layers_paged(
    params: Params,
    cfg: LlamaConfig,
    tokens: jax.Array,  # [N, C]
    offsets: jax.Array,  # [N]
    valid: jax.Array,  # [N]
    slots: jax.Array,  # [N] decode-slot index per row (page-table row)
    tables: jax.Array,  # [B, Pmax] page tables for ALL slots
    caches: list,
    window: int,
    page_size: int,
    quant_kernel: Optional[bool] = None,
    tp=None,
    page_kernel: Optional[str] = None,
) -> Tuple[jax.Array, list]:
    """``_chunk_layers`` over the page pool: the same masking, with
    cache coordinates routed through the page tables and the attention
    window gathered from the pool. Dead tokens (past a row's ``valid``:
    cached-prefix skips, finished rows, padding, rejected drafts) write
    to the scratch page, as the packed walk's do, so a page some request
    holds is never written but by its own live tokens.

    ``page_kernel`` (None | 'compiled' | 'interpret') swaps the
    attention READ for the ragged Pallas kernel
    (ops/page_attention.py): same post-write pools, a walk over each
    row's live pages instead of the bucketed-W gather. Writes are
    identical either way. A chunk wider than the kernel's query-row cap
    (the narrow rung of chunked prefill; spec verify fits whole) reads
    as ``C // fold`` sub-rows of ``page_attention.query_fold`` queries,
    each at its own first position over its row's table: every sub-row's
    K/V is in the pool before the layer's read and the kernel clamps per
    query, so each query sees the keys the gather's mask gives it. A
    sub-row past ``valid`` reads one page from position 0 (discarded).
    The engine passes ``page_kernel`` only for widths whose fold
    ``supports_geometry`` accepts; a full prefill chunk stays on the
    gather."""
    N, C = tokens.shape
    quantized = "ks" in caches[0]
    packed = quantized and caches[0]["k"].dtype == jnp.uint8
    qfn = quantize_kv_int4 if packed else quantize_kv
    Pmax = tables.shape[1]
    S = Pmax * page_size
    W = min(window, S)
    positions = offsets[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]
    positions = jnp.minimum(positions, S - 1)
    tok_valid = jnp.arange(C, dtype=jnp.int32)[None, :] < valid[:, None]
    h = params["embed"][tokens]
    row_tables = tables[slots]  # [N, Pmax]
    phys = jnp.take_along_axis(row_tables, positions // page_size, axis=1)
    phys = jnp.where(tok_valid, phys, 0)  # dead tokens -> scratch
    sip = positions % page_size
    runs = TokenRuns(C * jnp.arange(N, dtype=jnp.int32), valid, offsets, row_tables, C)
    if page_kernel:
        heads = cfg.num_heads // (tp.shards if tp is not None else 1)
        fold = page_attention.query_fold(C, heads)
        read_tables, read_pos = row_tables, offsets
        if fold < C:
            sub = fold * jnp.arange(C // fold, dtype=jnp.int32)[None, :]
            read_pos = jnp.where(
                sub < valid[:, None], offsets[:, None] + sub, 0
            ).reshape(-1)
            read_tables = jnp.repeat(row_tables, C // fold, axis=0)
        # one ragged work list per dispatch, shared by every layer's read
        work = page_attention.page_work_list(
            read_tables, read_pos, fold, page_size
        )

    def kernel_read(q, ck, cv, cks=None, cvs=None):
        out = _paged_kernel_read(
            q.reshape((-1, fold) + q.shape[2:]), ck, cv, read_tables,
            read_pos, cks, cvs, interpret=(page_kernel == "interpret"),
            tp=tp, work=work,
        )
        return out.reshape(q.shape).astype(q.dtype)

    new_caches = []
    for lp, c in zip(params["layers"], caches):
        def attn(q, k, v, c=c):
            if quantized:
                kq, ksn = qfn(k)  # [N,C,Hkv,Dh(/2)], [N,C,Hkv]
                vq, vsn = qfn(v)
                ck = c["k"].at[phys, sip].set(kq)
                cv = c["v"].at[phys, sip].set(vq)
                cks, cvs = write_kv_scales((c["ks"], c["vs"]), (ksn, vsn), phys, sip, runs)
                new_caches.append({"k": ck, "v": cv, "ks": cks, "vs": cvs})
                if page_kernel:
                    return kernel_read(q, ck, cv, cks, cvs), ()
                out = _window_attention(
                    q, ck, cv, cks, cvs, row_tables, positions, W, page_size
                )
            else:
                ck = c["k"].at[phys, sip].set(k.astype(c["k"].dtype))
                cv = c["v"].at[phys, sip].set(v.astype(c["v"].dtype))
                new_caches.append({"k": ck, "v": cv})
                if page_kernel:
                    return kernel_read(q, ck, cv), ()
                out = _window_attention(
                    q, ck, cv, None, None, row_tables, positions, W, page_size
                )
            return out, ()

        h, _ = _block(h, lp, cfg, positions, attn, quant_kernel=quant_kernel, tp=tp)

    return h, new_caches


def extend_layers_packed(
    params: Params,
    cfg: LlamaConfig,
    tokens: jax.Array,  # [T] the wave's tokens on one axis, row after row
    starts: jax.Array,  # [R] where each row begins on that axis (ascending)
    counts: jax.Array,  # [R] live tokens of each row (0: a dead row)
    offsets: jax.Array,  # [R] cache position of each row's first token
    slots: jax.Array,  # [R] decode-slot index per row (page-table row)
    tables: jax.Array,  # [B, Pmax] page tables for ALL slots
    caches: list,
    page_size: int,
    *,
    seg: int,
    windows: Tuple[int, ...],
    window_index=0,
    n_rows=None,
    quant_kernel: Optional[bool] = None,
    tp=None,
    page_kernel: Optional[str] = None,
) -> Tuple[jax.Array, list]:
    """Chunked prefill of one wave on a PACKED token axis: each row's
    last live token's hidden state [R, D] (a dead row's is garbage the
    caller drops) and the updated pools.

    Row ``r`` of the wave holds tokens ``starts[r] .. starts[r] +
    counts[r] - 1`` of the axis, at cache positions ``offsets[r] ..``;
    a token no row claims (the gap behind a row, the axis' padded end)
    is dead. Everything per token sees ``[1, T, ...]``: embedding,
    norms, RoPE on each token's own position, the packed/int8 products
    (``M = T``), the K/V quantisation and the page write, where a dead
    token lands on the scratch page, so a page some request holds is
    never written but by its own live tokens.

    Only the attention READ is per row. Each row's queries are gathered
    out of the axis to ``[R, seg, Hq, Dh]`` (``seg``: the most tokens a
    row can hold, static), read against the row's own cache, and
    gathered back. ``page_kernel`` reads all ``R`` rows through the
    ragged kernel, folded into sub-rows under its query-row cap exactly
    as ``_chunk_layers_paged`` folds them (a dead sub-row walks one
    page). Otherwise the read is the gather over a static window:
    ``windows[window_index]``, chosen INSIDE the program (``lax.switch``
    over the few power-of-two rungs, so the window multiplies no
    executables), over rows ``0 .. n_rows - 1`` one at a time (a
    ``fori_loop`` to a traced bound: a wave of one live row pays one
    row's scores), or over all ``R`` at once where ``n_rows`` is None.
    Every query sees the keys ``_chunk_layers_paged`` gives it, through
    the same softmax in the same dtypes."""
    T = tokens.shape[0]
    R = starts.shape[0]
    quantized = "ks" in caches[0]
    packed = quantized and caches[0]["k"].dtype == jnp.uint8
    qfn = quantize_kv_int4 if packed else quantize_kv
    S = tables.shape[1] * page_size
    t = jnp.arange(T, dtype=jnp.int32)
    tok_row = jnp.maximum(jnp.sum(t[:, None] >= starts[None, :], axis=1) - 1, 0)
    in_row = t - starts[tok_row]
    tok_live = (in_row >= 0) & (in_row < counts[tok_row])
    positions = jnp.clip(offsets[tok_row] + in_row, 0, S - 1)  # [T]
    row_tables = tables[slots]  # [R, Pmax]
    phys = jnp.where(tok_live, row_tables[tok_row, positions // page_size], 0)
    sip = jnp.where(tok_live, positions % page_size, t % page_size)
    phys, sip = phys[None], sip[None]  # [1, T]
    runs = TokenRuns(starts, counts, offsets, row_tables, seg)
    # the read's two index maps: a row's queries out of the axis, and
    # each token's place among the rows' results
    lane = jnp.arange(seg, dtype=jnp.int32)[None, :]
    q_index = jnp.minimum(starts[:, None] + lane, T - 1)  # [R, seg]
    row_pos = jnp.minimum(offsets[:, None] + lane, S - 1)  # [R, seg]
    back = tok_row * seg + jnp.clip(in_row, 0, seg - 1)  # [T]
    if page_kernel:
        heads = cfg.num_heads // (tp.shards if tp is not None else 1)
        fold = page_attention.query_fold(seg, heads)
        read_tables, read_pos = row_tables, offsets
        if fold < seg:
            sub = fold * jnp.arange(seg // fold, dtype=jnp.int32)[None, :]
            read_pos = jnp.where(
                sub < counts[:, None], offsets[:, None] + sub, 0
            ).reshape(-1)
            read_tables = jnp.repeat(row_tables, seg // fold, axis=0)
        # one ragged work list per dispatch, shared by every layer's read
        work = page_attention.page_work_list(
            read_tables, read_pos, fold, page_size
        )

    def rows_read(W):
        def read(q_rows, pools):
            if n_rows is None:
                return _window_attention(
                    q_rows, *pools, row_tables, row_pos, W, page_size
                )

            def body(r, out):
                one = lambda x: jax.lax.dynamic_slice_in_dim(x, r, 1, 0)
                o = _window_attention(
                    one(q_rows), *pools, one(row_tables), one(row_pos), W,
                    page_size,
                )
                return jax.lax.dynamic_update_slice_in_dim(out, o, r, 0)

            return jax.lax.fori_loop(
                0, n_rows, body, jnp.zeros_like(q_rows)
            )

        return read

    new_caches = []
    h = params["embed"][tokens[None]]  # [1, T, D]
    for lp, c in zip(params["layers"], caches):
        def attn(q, k, v, c=c):
            if quantized:
                (kq, ksn), (vq, vsn) = qfn(k), qfn(v)
                cks, cvs = write_kv_scales((c["ks"], c["vs"]), (ksn, vsn), phys, sip, runs)
                new = {
                    "k": c["k"].at[phys, sip].set(kq),
                    "v": c["v"].at[phys, sip].set(vq),
                    "ks": cks, "vs": cvs,
                }
                pools = (new["k"], new["v"], new["ks"], new["vs"])
            else:
                new = {
                    "k": c["k"].at[phys, sip].set(k.astype(c["k"].dtype)),
                    "v": c["v"].at[phys, sip].set(v.astype(c["v"].dtype)),
                }
                pools = (new["k"], new["v"], None, None)
            new_caches.append(new)
            q_rows = q[0][q_index]  # [R, seg, Hq, Dh]
            if page_kernel:
                out = _paged_kernel_read(
                    q_rows.reshape((-1, fold) + q_rows.shape[2:]), *pools[:2],
                    read_tables, read_pos, *pools[2:],
                    interpret=(page_kernel == "interpret"), tp=tp, work=work,
                ).reshape(q_rows.shape).astype(q.dtype)
            else:
                out = jax.lax.switch(
                    window_index, [rows_read(W) for W in windows],
                    q_rows, pools,
                )
            return out.reshape((R * seg,) + out.shape[2:])[back][None], ()

        h, _ = _block(
            h, lp, cfg, positions[None], attn, quant_kernel=quant_kernel,
            tp=tp,
        )
    last = jnp.clip(starts + counts - 1, 0, T - 1)
    return h[0][last], new_caches


def extend_layers_paged(
    params: Params,
    cfg: LlamaConfig,
    tokens: jax.Array,
    offsets: jax.Array,
    valid: jax.Array,
    slots: jax.Array,
    tables: jax.Array,
    caches: list,
    window: int,
    page_size: int,
    quant_kernel: Optional[bool] = None,
    tp=None,
    page_kernel: Optional[str] = None,
) -> Tuple[jax.Array, list]:
    """``extend_layers`` over the page pool for a RECTANGLE of rows
    (chunked prefill as ``[rows, width]``): the packed walk over
    ``T = rows x width`` tokens, row ``r`` starting at ``r x width``,
    read at the one static ``window`` over all rows at once.

    ``page_kernel`` serves the read through the ragged kernel, folded
    into sub-rows under the kernel's query-row cap."""
    N, C = tokens.shape
    S = tables.shape[1] * page_size
    return extend_layers_packed(
        params, cfg, tokens.reshape(-1), C * jnp.arange(N, dtype=jnp.int32),
        valid, offsets, slots, tables, caches, page_size, seg=C,
        windows=(min(window, S),), quant_kernel=quant_kernel, tp=tp,
        page_kernel=page_kernel,
    )


def verify_layers_paged(
    params: Params,
    cfg: LlamaConfig,
    tokens: jax.Array,
    offsets: jax.Array,
    valid: jax.Array,
    slots: jax.Array,
    tables: jax.Array,
    caches: list,
    window: int,
    page_size: int,
    quant_kernel: Optional[bool] = None,
    tp=None,
    page_kernel: Optional[str] = None,
) -> Tuple[jax.Array, list]:
    """Speculative-decoding verify: the chunked extend pass with logits
    at EVERY chunk position, returning ([N, C, V], updated caches).
    Position j's logits are the model's next-token distribution after
    the prefix ending at ``offsets + j``, so scoring K draft tokens
    plus the carried last token costs ONE dispatch instead of K+1.
    Positions past ``valid`` are value-masked no-ops, so rejected draft
    rows are garbage above the accepted frontier and the next verify
    chunk overwrites them before any query can attend that far.

    ``page_kernel`` runs the K+1-wide verify chunk through the ragged
    kernel's multi-query rows when the engine's geometry probe allows
    it (``page_attention.supports_geometry(query_len=K+1)``)."""
    h, new_caches = _chunk_layers_paged(
        params, cfg, tokens, offsets, valid, slots, tables, caches,
        window, page_size, quant_kernel=quant_kernel, tp=tp,
        page_kernel=page_kernel,
    )
    logits = _head(params, h, cfg, quant_kernel, tp=tp)
    return logits, new_caches


def decode_layers_paged(
    params: Params,
    cfg: LlamaConfig,
    tokens: jax.Array,  # [B]
    positions: jax.Array,  # [B] (dead slots pre-zeroed by the engine)
    live: jax.Array,  # [B] bool
    tables: jax.Array,  # [B, Pmax]
    caches: list,
    window: Optional[int] = None,
    page_size: int = 128,
    quant_kernel: Optional[bool] = None,
    tp=None,
    page_kernel: Optional[str] = None,
) -> Tuple[jax.Array, list]:
    """One decode step over the page pool; returns (logits [B, V],
    updated pools). bf16 runs the einsum attention; int8 runs
    ``ops/decode_attention.decode_attention_xla``'s dequant formula
    over the gathered window. Dead rows write the scratch page.

    ``page_kernel`` (None | 'compiled' | 'interpret') serves the read
    through ops/page_attention.py instead of the XLA gather: identical
    pool writes, a walk over each row's live pages, online
    softmax in f32 — same dequant formula, blockwise accumulation
    order (float-tolerance vs the gather; chip_smoke.py and
    tests/test_page_attention.py hold the two together)."""
    B = tokens.shape[0]
    quantized = "ks" in caches[0]
    packed = quantized and caches[0]["k"].dtype == jnp.uint8
    qfn = quantize_kv_int4 if packed else quantize_kv
    Hkv = cfg.num_kv_heads
    G = cfg.num_heads // Hkv
    Pmax = tables.shape[1]
    S = Pmax * page_size
    W = min(window or S, S)
    Pw = W // page_size
    h = params["embed"][tokens[:, None]]
    pos2 = positions[:, None]  # [B, 1]
    phys = jnp.take_along_axis(tables, pos2 // page_size, axis=1)  # [B, 1]
    phys = jnp.where(live[:, None], phys, 0)
    sip = pos2 % page_size
    runs = TokenRuns(None, live.astype(jnp.int32), positions, tables, 1)
    mask = jnp.arange(W, dtype=jnp.int32)[None, None, :] <= pos2[:, :, None]
    # one ragged work list per step, shared by every layer's read
    work = (
        page_attention.page_work_list(
            tables, positions, 1, page_size,
            page_attention.pages_per_step(caches[0]["k"], caches[0].get("ks")),
        )
        if page_kernel else None
    )
    new_caches = []
    for lp, c in zip(params["layers"], caches):
        def attn(q, k, v, c=c):
            if quantized:
                kq, ksn = qfn(k)  # [B,1,Hkv,Dh(/2)], [B,1,Hkv]
                vq, vsn = qfn(v)
                ck = c["k"].at[phys, sip].set(kq)
                cv = c["v"].at[phys, sip].set(vq)
                cks, cvs = write_kv_scales((c["ks"], c["vs"]), (ksn, vsn), phys, sip, runs)
                new_caches.append({"k": ck, "v": cv, "ks": cks, "vs": cvs})
                if page_kernel:
                    out = _paged_kernel_read(
                        q, ck, cv, tables, positions, cks, cvs,
                        interpret=(page_kernel == "interpret"), tp=tp,
                        work=work,
                    ).astype(q.dtype)
                    return out, ()
                # decode_attention_xla's math over the gathered window:
                # head-major transpose, int->f32 dequant, f32 einsums
                # (int4 windows nibble-unpack first — same dequant
                # formula as the kernel's epilogue).
                gk = _gather_page_window(ck, tables, Pw, page_size)
                gv = _gather_page_window(cv, tables, Pw, page_size)
                if packed:
                    gk = unpack_int4(gk)
                    gv = unpack_int4(gv)
                kd = jnp.swapaxes(gk, 1, 2).astype(jnp.float32) * jnp.swapaxes(
                    gather_kv_scales(cks, tables, Pw, page_size), 1, 2
                )[..., None]  # [B, Hkv, W, Dh]
                vd = jnp.swapaxes(gv, 1, 2).astype(jnp.float32) * jnp.swapaxes(
                    gather_kv_scales(cvs, tables, Pw, page_size), 1, 2
                )[..., None]
                qg = q.reshape(B, 1, Hkv, G, cfg.head_dim).astype(jnp.float32)
                sc = jnp.einsum("btkgd,bksd->bkgts", qg, kd) / math.sqrt(
                    cfg.head_dim
                )
                sc = jnp.where(mask[:, None, None], sc, -1e30)
                p = jax.nn.softmax(sc, axis=-1)
                out = jnp.einsum("bkgts,bksd->btkgd", p, vd)
                out = out.reshape(B, 1, cfg.num_heads, cfg.head_dim).astype(
                    q.dtype
                )
            else:
                ck = c["k"].at[phys, sip].set(k)
                cv = c["v"].at[phys, sip].set(v)
                new_caches.append({"k": ck, "v": cv})
                if page_kernel:
                    out = _paged_kernel_read(
                        q, ck, cv, tables, positions,
                        interpret=(page_kernel == "interpret"), tp=tp,
                        work=work,
                    ).astype(q.dtype)
                    return out, ()
                out = _attention(
                    q,
                    _gather_page_window(ck, tables, Pw, page_size),
                    _gather_page_window(cv, tables, Pw, page_size),
                    mask,
                )
            return out, ()

        h, _ = _block(h, lp, cfg, pos2, attn, quant_kernel=quant_kernel, tp=tp)
    logits = _head(params, h, cfg, quant_kernel, tp=tp)
    return logits[:, 0, :], new_caches
