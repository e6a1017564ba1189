"""Weight quantization for serving: int8 storage with per-channel scales.

Serves the reference's 70B-class deployments (320 GB GPU memory in the
reference, docs/support-matrix.md:43-46) on small-HBM TPU chips: int8
weight-only quantization halves both HBM capacity (fits llama3-8b on one
16 GB v5e chip, 70B int8 + TP=8 on a v5e-8) and — through the Pallas
kernel in ops/int8_matmul.py — the per-decode-step weight streaming that
bounds token latency.

Packed layout per projection (stacked on the leading layer axis):
  {"q": int8 [L, K_pad, F_pad], "scale": float32 [L, 1, F]}
K is padded to K_ALIGN (128 — the kernel's K blocks sit on the 128-lane
dim, so only 128-aligned blockings exist) and F to the kernel's F tile
(512); scale keeps the logical F so consumers recover output shape.

Tensor-parallel packs (``tp_shards`` > 1) pad PER SHARD instead of at the
global end, so a NamedSharding split along the sharded axis hands every
device a self-contained kernel tile (parallel/tp_kernels.py runs the
Pallas kernel on each tile via shard_map — the reference keeps its
TRT-LLM kernels at any INFERENCE_GPU_COUNT, docker-compose-nim-ms.
yaml:20, and so must we):
- kind="column" (wq/wk/wv/w_gate/w_up/lm_head — Megatron column-parallel,
  output axis sharded): F splits into tp_shards blocks, each padded to
  F_BLK ⇒ q [..., K_pad, tp_shards * F_shard_pad]; scale keeps [..., 1, F].
- kind="row" (wo/w_down — row-parallel, contraction axis sharded): K
  splits per shard, each padded to K_ALIGN ⇒ q [..., tp_shards * K_shard_pad,
  F_pad]; the x rows a shard owns line up with its tile's real rows.
A tp pack is NOT readable by the global-slicing consumers
(int8_matmul_xla / dequantize_int8) unless the per-shard layout happens
to coincide with the global one — pass the same tp_shards/kind to
dequantize_int8, and route matmuls through tp_kernels.packed_matmul_tp.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from generativeaiexamples_tpu.ops.int8_matmul import F_BLK, K_ALIGN

def _pad_to(n: int, mult: int) -> int:
    return (n + mult - 1) // mult * mult


def _layout(q, tp_shards: int, kind: str):
    """Pad an unpadded int8 [..., K, F] matrix into the (possibly
    per-shard) kernel layout. Works on jnp and numpy arrays alike (the
    ops dispatch on the input type via jnp)."""
    K, F = q.shape[-2], q.shape[-1]
    lead = [(0, 0)] * (q.ndim - 2)
    if tp_shards <= 1:
        return jnp.pad(
            q, lead + [(0, _pad_to(K, K_ALIGN) - K), (0, _pad_to(F, F_BLK) - F)]
        )
    if kind == "column":
        if F % tp_shards:
            raise ValueError(f"column pack: F={F} not divisible by {tp_shards}")
        Fl = F // tp_shards
        pad = lead + [(0, _pad_to(K, K_ALIGN) - K), (0, _pad_to(Fl, F_BLK) - Fl)]
        parts = jnp.split(q, tp_shards, axis=-1)
        return jnp.concatenate([jnp.pad(p, pad) for p in parts], axis=-1)
    if kind == "row":
        if K % tp_shards:
            raise ValueError(f"row pack: K={K} not divisible by {tp_shards}")
        Kl = K // tp_shards
        pad = lead + [(0, _pad_to(Kl, K_ALIGN) - Kl), (0, _pad_to(F, F_BLK) - F)]
        parts = jnp.split(q, tp_shards, axis=-2)
        return jnp.concatenate([jnp.pad(p, pad) for p in parts], axis=-2)
    raise ValueError(f"kind must be 'column' or 'row', got {kind!r}")


def quantize_int8(
    w: jax.Array, tp_shards: int = 1, kind: str = "column"
) -> Dict[str, jax.Array]:
    """Symmetric per-output-channel int8 packing of [..., K, F] weights."""
    w32 = w.astype(jnp.float32)
    scale = jnp.max(jnp.abs(w32), axis=-2, keepdims=True) / 127.0
    scale = jnp.maximum(scale, 1e-8)
    q = jnp.clip(jnp.round(w32 / scale), -127, 127).astype(jnp.int8)
    return {"q": _layout(q, tp_shards, kind), "scale": scale}


def dequantize_int8(
    packed: Dict[str, jax.Array],
    dtype=jnp.bfloat16,
    k_features: int | None = None,
    tp_shards: int = 1,
    kind: str = "column",
) -> jax.Array:
    """Reconstruct bf16 weights. F padding is always cut (the logical F
    lives in the scale); K padding is cut only when the caller passes
    ``k_features`` — the pack stores no logical K, so the default keeps
    the K_pad zero rows (harmless for x @ w with a matching-padded x,
    but pass k_features to recover the exact original shape). A
    tensor-parallel pack must be read with the SAME tp_shards/kind it was
    built with (per-shard padding sits between the shards' real blocks)."""
    q = packed["q"]
    F = packed["scale"].shape[-1]
    if tp_shards > 1:
        if kind == "column":
            Fl = F // tp_shards
            parts = jnp.split(q, tp_shards, axis=-1)
            q = jnp.concatenate([p[..., :Fl] for p in parts], axis=-1)
        elif kind == "row":
            if k_features is None:
                raise ValueError("row-parallel dequant needs k_features")
            Kl = k_features // tp_shards
            parts = jnp.split(q, tp_shards, axis=-2)
            q = jnp.concatenate([p[..., :Kl, :] for p in parts], axis=-2)
            k_features = None  # per-shard padding already cut
        else:
            raise ValueError(f"kind must be 'column' or 'row', got {kind!r}")
    q = q[..., : (k_features or q.shape[-2]), :F]
    return (q.astype(jnp.float32) * packed["scale"]).astype(dtype)


def _shard_blocks(K: int, F: int, tp_shards: int, kind: str):
    """(dst_k, dst_f, src_k, src_f) copy blocks for the tp layout, plus
    the padded destination (K_dst, F_dst). Single source of truth for the
    numpy packers; tp_shards=1 degenerates to one end-padded block."""
    if tp_shards <= 1:
        return (
            _pad_to(K, K_ALIGN),
            _pad_to(F, F_BLK),
            [((0, K), (0, F), (0, K), (0, F))],
        )
    if kind == "column":
        if F % tp_shards:
            raise ValueError(f"column pack: F={F} not divisible by {tp_shards}")
        Fl = F // tp_shards
        Flp = _pad_to(Fl, F_BLK)
        K_dst = _pad_to(K, K_ALIGN)
        blocks = [
            ((0, K), (i * Flp, i * Flp + Fl), (0, K), (i * Fl, (i + 1) * Fl))
            for i in range(tp_shards)
        ]
        return K_dst, tp_shards * Flp, blocks
    if kind == "row":
        if K % tp_shards:
            raise ValueError(f"row pack: K={K} not divisible by {tp_shards}")
        Kl = K // tp_shards
        Klp = _pad_to(Kl, K_ALIGN)
        F_dst = _pad_to(F, F_BLK)
        blocks = [
            ((i * Klp, i * Klp + Kl), (0, F), (i * Kl, (i + 1) * Kl), (0, F))
            for i in range(tp_shards)
        ]
        return tp_shards * Klp, F_dst, blocks
    raise ValueError(f"kind must be 'column' or 'row', got {kind!r}")


def _quantize_int8_host(w, tp_shards: int = 1, kind: str = "column") -> Dict[str, jax.Array]:
    """Streaming numpy quantization for host-staged weights.

    jnp math on the single-core CPU backend takes ~3 min for a 1B model
    (bf16 emulation + full-tree temporaries); this processes one leading
    slice at a time in float32 numpy (~10x faster, flat memory) and is
    bit-compatible with quantize_int8 up to f32 rounding.
    """
    import numpy as np

    arr = np.asarray(w)
    lead = arr.shape[:-2]
    K, F = arr.shape[-2], arr.shape[-1]
    K_dst, F_dst, blocks = _shard_blocks(K, F, tp_shards, kind)
    flat = arr.reshape((-1, K, F))
    q = np.zeros((flat.shape[0], K_dst, F_dst), np.int8)
    scale = np.zeros((flat.shape[0], 1, F), np.float32)
    for i in range(flat.shape[0]):
        w32 = flat[i].astype(np.float32)
        s = np.maximum(np.abs(w32).max(axis=0, keepdims=True) / 127.0, 1e-8)
        qi = np.clip(np.round(w32 / s), -127, 127).astype(np.int8)
        for (dk, df, sk, sf) in blocks:
            q[i, dk[0] : dk[1], df[0] : df[1]] = qi[sk[0] : sk[1], sf[0] : sf[1]]
        scale[i] = s
    return {
        "q": jnp.asarray(q.reshape(*lead, K_dst, F_dst)),
        "scale": jnp.asarray(scale.reshape(*lead, 1, F)),
    }


# Megatron kind per projection: column-parallel shards the output axis,
# row-parallel the contraction axis (parallel/sharding.py param_specs).
PACK_KINDS: Dict[str, str] = {
    "wq": "column",
    "wk": "column",
    "wv": "column",
    "w_gate": "column",
    "w_up": "column",
    "wqkv": "column",
    "w_gateup": "column",
    "lm_head": "column",
    "wo": "row",
    "w_down": "row",
}


def quantize_params_int8(params: Dict[str, Any], tp_shards: int = 1) -> Dict[str, Any]:
    """Pack the big projection matrices as int8; the rest stays bf16.

    Single-device (tp_shards=1): QKV and gate|up are fused along the
    output axis into single packed matmuls ("wqkv", "w_gateup") —
    per-decode-step kernel dispatches drop from 7 to 4 per layer, and
    fixed per-pallas_call overhead (~10us) is what bounds int8 decode
    once weight bytes are halved. Per-channel scales are unaffected by
    concatenation. models/llama.py's ``_block`` detects the fused keys
    and slices Q/K/V (gate/up) from the output.

    Tensor-parallel (tp_shards>1): projections stay UNFUSED — sharding a
    fused output axis would hand each device a mixed slab (device 0 gets
    only Q features etc.) and force an all-to-all before the head
    reshape; unfused column packs align shards with heads for free. Each
    pack is laid out per shard (see module docstring) so
    parallel/tp_kernels.py can run the Pallas kernel on local tiles.
    """

    def on_host(x) -> bool:
        try:
            return next(iter(x.devices())).platform == "cpu"
        except Exception:  # noqa: BLE001 - plain numpy input
            return True

    def pack(w, kind):
        if on_host(w):
            return _quantize_int8_host(w, tp_shards, kind)
        return quantize_int8(w, tp_shards, kind)

    def concat(ws):
        import numpy as np

        if all(on_host(w) for w in ws):
            return np.concatenate([np.asarray(w) for w in ws], axis=-1)
        return jnp.concatenate(ws, axis=-1)

    out = dict(params)
    layers = dict(params["layers"])
    fuse = tp_shards <= 1
    if fuse and all(
        k in layers and not isinstance(layers[k], dict) for k in ("wq", "wk", "wv")
    ):
        layers["wqkv"] = pack(
            concat([layers.pop("wq"), layers.pop("wk"), layers.pop("wv")]), "column"
        )
    if fuse and all(
        k in layers and not isinstance(layers[k], dict) for k in ("w_gate", "w_up")
    ):
        layers["w_gateup"] = pack(
            concat([layers.pop("w_gate"), layers.pop("w_up")]), "column"
        )
    for key in ("wq", "wk", "wv", "w_gate", "w_up", "wo", "w_down"):
        if key in layers and not isinstance(layers[key], dict):
            layers[key] = pack(layers[key], PACK_KINDS[key])
    out["layers"] = layers
    if "lm_head" in out and not isinstance(out["lm_head"], dict):
        out["lm_head"] = pack(out["lm_head"], "column")
    return out


def init_packed_params_int8(cfg, seed: int = 0, dtype=jnp.bfloat16, tp_shards: int = 1):
    """Random-init parameters directly in packed int8 form.

    The no-checkpoint serving path (proxy benchmarks) does not need real
    weights — only the right shapes/dtypes for the compute profile.
    Generating f32 normals and quantizing takes ~15 min for 8B on the
    single-core host; drawing int8 uniforms directly (scales chosen so
    dequantized std matches init_params' scaled-normal init: uniform
    int8 has std ~73) takes seconds per GB. Shapes and stds come from
    models/llama.init_spec — the same source init_params uses — and the
    pytree structure matches quantize_params_int8(init_params(cfg),
    tp_shards) (fused at tp_shards=1, unfused per-shard tiles above).
    ``dtype`` applies to the non-quantized leaves (embed, norms).
    """
    import numpy as np

    from generativeaiexamples_tpu.models.llama import init_spec

    rng = np.random.default_rng(seed)
    spec = init_spec(cfg)
    L, h = cfg.num_layers, cfg.hidden_size

    def normal(name):
        shape, scale = spec[name]
        w = rng.standard_normal(size=shape, dtype=np.float32) * np.float32(scale)
        return jnp.asarray(w.astype(jnp.dtype(dtype)))

    def packed(*names, kind="column"):
        # Fuse the named dense specs along the output axis, like
        # quantize_params_int8 does for Q|K|V and gate|up.
        shapes = [spec[n] for n in names]
        lead = shapes[0][0][:-2]
        k_dim = shapes[0][0][-2]
        f_dim = sum(s[0][-1] for s in shapes)
        K_dst, F_dst, blocks = _shard_blocks(k_dim, f_dim, tp_shards, kind)
        qarr = np.zeros((*lead, K_dst, F_dst), np.int8)
        # One draw per NAMED weight, in the unfused order: the fused
        # (tp_shards=1) and per-shard (TP) packs then hold the same
        # logical model, so engines of different TP width can be
        # compared on random-init weights (chip_smoke.py --chips 4).
        # Each draw is cast straight into its columns of the pack: an
        # int8 copy of every draw plus their concatenation cost a third
        # of a 7B engine's build time on the host.
        off = 0
        for s in shapes:
            f_n = s[0][-1]
            draw = rng.integers(-127, 128, size=(*lead, k_dim, f_n), dtype=np.int16)
            for (dk, df, sk, sf) in blocks:
                lo, hi = max(sf[0], off), min(sf[1], off + f_n)
                if lo < hi:
                    d0 = df[0] + lo - sf[0]
                    qarr[..., dk[0] : dk[1], d0 : d0 + hi - lo] = draw[
                        ..., sk[0] : sk[1], lo - off : hi - off
                    ]
            off += f_n
        scale = np.concatenate(
            [
                np.full((*lead, 1, s[0][-1]), s[1] / 73.0, np.float32)
                for s in shapes
            ],
            axis=-1,
        )
        return {"q": jnp.asarray(qarr), "scale": jnp.asarray(scale)}

    layers: Dict[str, Any] = {
        "attn_norm": jnp.ones((L, h), dtype),
        "mlp_norm": jnp.ones((L, h), dtype),
    }
    if tp_shards <= 1:
        layers["wqkv"] = packed("wq", "wk", "wv")
        layers["w_gateup"] = packed("w_gate", "w_up")
    else:  # unfused under TP — shards must align with heads (see above)
        for name in ("wq", "wk", "wv", "w_gate", "w_up"):
            layers[name] = packed(name, kind=PACK_KINDS[name])
    layers["wo"] = packed("wo", kind="row")
    layers["w_down"] = packed("w_down", kind="row")
    params = {
        "embed": normal("embed"),
        "layers": layers,
        "final_norm": jnp.ones((h,), dtype),
    }
    if "lm_head" in spec:
        params["lm_head"] = packed("lm_head")
    return params
