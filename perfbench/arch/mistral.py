"""The adapter of the Mistral/Llama decoder block (contract: ``perfbench/arch/__init__.py``).

**Registration.** The engine resolves an architecture only through
``llama.PRESETS``, so ``register`` writes the configuration file's
published sizes there under the configuration's name.

**The plain reference**: a Mistral/Llama-style decoder in float32
jax.numpy, written from the published description of the architecture
(pre-norm residual blocks; RMSNorm; rotary position embedding applied to
halves of each head, ``rotate_half`` as in the model's public
implementation; grouped-query attention with a causal mask; SwiGLU; an
untied output head). It uses no function of the program's ``ops/``,
``engine/`` or ``models/``: no kernel, no cache, no batching — the whole
sequence is recomputed, every position's logits come from one pass, on
the engine's OWN int8 weights, dequantised to float32 layer by layer
(the reference never holds more than one layer in float32).

TOLERANCE, as max|engine - reference| / max|reference| over a prompt's
last-position logits: the engine computes activations in bfloat16 (8
mantissa bits, relative rounding 2^-8 = 0.0039) through 32 layers of
four matrix products each, the reference in float32 from the same
integers. Independent roundings add as a random walk: 0.0039 x
sqrt(4 x 32) = 0.044 is where roundings alone could take it. Measured
on the chip at the published widths (PR 24, every run, the prompts and
weights being fixed): 0.0187 and 0.0213; at the 2-layer test size
0.0075 and 0.0138. Serving the weights in int4 instead (relative step
1/7 against 1/127), dropping a layer, or a wrong rotary base each move
the logits by more than a tenth of their range on random weights (the
test suite injects all three), so 0.04 leaves a later change of
summation order its room and still separates the two cases.

**Bytes and operations a step needs**, from the configuration's shapes:
kept with the benchmark so that no PR which claims a gain can change the
count. Every function takes the configuration file's dict (the published
``config.json`` keys at its top level plus ``engine``). The weights are
int8 matrices, so the operations are held against the int8 peak.
"""
from __future__ import annotations

import contextlib
from typing import Any, Callable, Dict, List, Sequence

import numpy as np

from perfbench.reference import dense as _dense

TOLERANCE = 0.04


# --------------------------------------------------------------------------- #
# The engine's side: registration and its own prefill forward


def llama_config(cfg: dict):
    from generativeaiexamples_tpu.models.llama import LlamaConfig

    return LlamaConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"], num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"], num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]), max_seq_len=cfg["max_position_embeddings"],
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
    )


def register(cfg: dict) -> None:
    from generativeaiexamples_tpu.models import llama

    llama.PRESETS[cfg["name"]] = llama_config(cfg)


def engine_prefill_logits(eng, prompts, on_tpu: bool):
    """Last-prompt-position logits through the engine's own prefill
    forward with the kernel flags the engine resolved (as chip_smoke.py
    obtains them for its TP comparison)."""
    import jax
    import jax.numpy as jnp

    from generativeaiexamples_tpu.models import llama
    from generativeaiexamples_tpu.parallel.mesh import mesh_context

    T = max(128, -(-max(len(p) for p in prompts) // 128) * 128)
    tok = np.zeros((len(prompts), T), np.int32)
    for i, p in enumerate(prompts):
        tok[i, : len(p)] = p
    lengths = np.asarray([len(p) for p in prompts], np.int32)
    use_flash = None if (eng._mesh.size == 1 or eng._tp is not None) else False

    def fwd(params, tokens, lens):
        return llama.prefill_layers(
            params, eng.model_config, tokens, lens, use_flash=use_flash,
            quant_kernel=eng._quant_kernel, tp=eng._tp, interpret=not on_tpu,
        )[0]

    with mesh_context(eng._mesh):
        return np.asarray(jax.jit(fwd)(eng.params, jnp.asarray(tok), jnp.asarray(lengths)), np.float32)


# --------------------------------------------------------------------------- #
# The plain float32 reference


def rms_norm(x, weight, eps: float):
    import jax.numpy as jnp

    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)) * weight


def rotary(x, positions, theta: float):
    """x [T, H, D]; pairs (i, i + D/2) rotate by position * theta^(-2i/D)."""
    import jax.numpy as jnp

    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]  # [T, D/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def layer_forward(h, w: Dict[str, Any], cfg: Dict[str, Any]):
    """One decoder layer on one sequence h [T, hidden], all float32."""
    import jax
    import jax.numpy as jnp

    T = h.shape[0]
    nh, nkv, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    eps = cfg["rms_norm_eps"]
    pos = jnp.arange(T)
    x = rms_norm(h, w["attn_norm"], eps)
    q = rotary((x @ w["wq"]).reshape(T, nh, d), pos, cfg["rope_theta"])
    k = rotary((x @ w["wk"]).reshape(T, nkv, d), pos, cfg["rope_theta"])
    v = (x @ w["wv"]).reshape(T, nkv, d)
    group = nh // nkv
    k = jnp.repeat(k, group, axis=1)  # each KV head serves `group` query heads
    v = jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("thd,shd->hts", q, k) / np.sqrt(d)
    causal = pos[:, None] >= pos[None, :]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    attn = jnp.einsum("hts,shd->thd", probs, v).reshape(T, nh * d)
    h = h + attn @ w["wo"]
    x = rms_norm(h, w["mlp_norm"], eps)
    gate = x @ w["w_gate"]
    h = h + ((gate * jax.nn.sigmoid(gate)) * (x @ w["w_up"])) @ w["w_down"]
    return h


def forward(tokens_list: Sequence[Sequence[int]], cfg: Dict[str, Any], embed,
            layer_weights: Callable[[int], Dict[str, Any]], final_norm, lm_head,
            device=None) -> List[np.ndarray]:
    """Logits [T, vocab] of every position of every sequence. Each layer's
    weights are fetched once (``layer_weights(i)``: float32 arrays, or
    ``(int8, scale)`` pairs that are dequantised to float32 here), applied
    to all sequences, then dropped; the next layer is fetched meanwhile."""
    from concurrent.futures import ThreadPoolExecutor

    import jax
    import jax.numpy as jnp

    ctx = jax.default_device(device) if device is not None else contextlib.nullcontext()
    L = cfg["num_hidden_layers"]
    with ctx, jax.default_matmul_precision("highest"), ThreadPoolExecutor(1) as pool:
        emb = np.asarray(embed, np.float32)
        hs = [jnp.asarray(emb[np.asarray(t)]) for t in tokens_list]
        dense = jax.jit(lambda w: {k: _dense(v) for k, v in w.items()})  # once a layer, not once a sequence
        step = jax.jit(lambda h, w: layer_forward(h, w, cfg))
        nxt = pool.submit(layer_weights, 0)
        for i in range(L):
            w = dense(nxt.result())
            if i + 1 < L:
                nxt = pool.submit(layer_weights, i + 1)
            hs = [step(h, w) for h in hs]
            for h in hs:
                h.block_until_ready()
            del w
        fn = jnp.asarray(np.asarray(final_norm, np.float32))
        head = _dense(tuple(jnp.asarray(x) for x in lm_head) if isinstance(lm_head, tuple) else jnp.asarray(lm_head))
        return [np.asarray(rms_norm(h, fn, cfg["rms_norm_eps"]) @ head) for h in hs]


def _pad(n: int, mult: int) -> int:
    return -(-n // mult) * mult


def unpack(pack: Dict[str, Any], k: int, f: int, lo: int = 0, tp: int = 1, kind: str = "column"):
    """Columns [lo, lo + f) of an int8 pack with a float32 scale per
    output channel, as ``(int8 [k, f], scale [1, f])``: the padding the
    kernels want is cut, the integers are untouched. A tensor-parallel
    pack (``tp`` > 1) is laid out per shard: a ``column`` pack splits the
    output axis into ``tp`` blocks each padded to 512, a ``row`` pack the
    contraction axis into ``tp`` blocks each padded to 128."""
    q = np.asarray(pack["q"])
    scale = np.asarray(pack["scale"], np.float32).reshape(1, -1)
    if tp > 1 and kind == "column":
        fl = scale.shape[-1] // tp
        flp = _pad(fl, 512)
        q = np.concatenate([q[:, i * flp:i * flp + fl] for i in range(tp)], axis=1)
    elif tp > 1:
        kl = k // tp
        klp = _pad(kl, 128)
        q = np.concatenate([q[i * klp:i * klp + kl] for i in range(tp)], axis=0)
    q = np.ascontiguousarray(q[:k, lo:lo + f])
    return q, np.ascontiguousarray(scale[:, lo:lo + f])


def engine_layer_weights(params: Dict[str, Any], cfg: Dict[str, Any], i: int, tp: int = 1) -> Dict[str, Any]:
    """Layer ``i`` of the engine's parameter tree (per-layer list or
    stacked, int8 packs; Q|K|V and gate|up fused along the output axis at
    tp=1, unfused per-shard packs above) as the reference's nine named
    weights, the matrices still as ``(int8, scale)`` pairs."""
    layers = params["layers"]
    if isinstance(layers, (list, tuple)):
        lp = layers[i]
    else:  # stacked on a leading layer axis
        lp = {
            k: ({kk: vv[i] for kk, vv in v.items()} if isinstance(v, dict) else v[i])
            for k, v in layers.items()
        }
    h, m = cfg["hidden_size"], cfg["intermediate_size"]
    qd = cfg["num_attention_heads"] * cfg["head_dim"]
    kvd = cfg["num_key_value_heads"] * cfg["head_dim"]
    out = {
        "attn_norm": np.asarray(lp["attn_norm"], np.float32),
        "mlp_norm": np.asarray(lp["mlp_norm"], np.float32),
        "wo": unpack(lp["wo"], qd, h, tp=tp, kind="row"),
        "w_down": unpack(lp["w_down"], m, h, tp=tp, kind="row"),
    }
    if "wqkv" in lp:
        fused = {"q": np.asarray(lp["wqkv"]["q"]), "scale": np.asarray(lp["wqkv"]["scale"])}
        out["wq"] = unpack(fused, h, qd)
        out["wk"] = unpack(fused, h, kvd, qd)
        out["wv"] = unpack(fused, h, kvd, qd + kvd)
        fused = {"q": np.asarray(lp["w_gateup"]["q"]), "scale": np.asarray(lp["w_gateup"]["scale"])}
        out["w_gate"] = unpack(fused, h, m)
        out["w_up"] = unpack(fused, h, m, m)
    else:
        out["wq"] = unpack(lp["wq"], h, qd, tp=tp)
        out["wk"] = unpack(lp["wk"], h, kvd, tp=tp)
        out["wv"] = unpack(lp["wv"], h, kvd, tp=tp)
        out["w_gate"] = unpack(lp["w_gate"], h, m, tp=tp)
        out["w_up"] = unpack(lp["w_up"], h, m, tp=tp)
    return out


def reference_logits(eng, cfg: Dict[str, Any], sequences: Sequence[Sequence[int]], tp: int = 1,
                     device=None) -> List[np.ndarray]:
    """``forward`` over the engine's own parameter tree."""
    params = eng.params
    head = params.get("lm_head")
    lm_head = (
        unpack(head, cfg["hidden_size"], cfg["vocab_size"], tp=tp)
        if isinstance(head, dict)
        else np.asarray(head if head is not None else np.asarray(params["embed"]).T, np.float32)
    )
    return forward(
        sequences, cfg,
        np.asarray(params["embed"], np.float32),
        lambda i: engine_layer_weights(params, cfg, i, tp),
        np.asarray(params["final_norm"], np.float32), lm_head,
        device=device,
    )


# --------------------------------------------------------------------------- #
# Bytes and operations of a decode step


def layer_weight_elements(cfg: Dict[str, Any]) -> int:
    """Matrix elements of one decoder layer: Q|K|V, O, gate|up, down."""
    h, m = cfg["hidden_size"], cfg["intermediate_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    return h * (q + 2 * kv) + q * h + h * 2 * m + m * h


def decode_weight_bytes(cfg: Dict[str, Any]) -> int:
    """Bytes of weights one decode step streams from HBM: the int8
    matrices of every layer and of the untied head (1 byte an element),
    their float32 per-output-channel scales, and the bf16 norm vectors.
    The embedding table is read one row per sequence and is counted in
    ``decode_step_bytes``."""
    h, m, L, v = (cfg["hidden_size"], cfg["intermediate_size"],
                  cfg["num_hidden_layers"], cfg["vocab_size"])
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    matrices = L * layer_weight_elements(cfg) + h * v
    scales = 4 * (L * ((q + 2 * kv) + h + 2 * m + h) + v)
    norms = 2 * (2 * L * h + h)
    return matrices + scales + norms


def kv_bytes_per_token(cfg: Dict[str, Any]) -> int:
    """int8 K and V of every layer plus one float32 scale per head,
    layer and K/V for one cached token."""
    L, kvh, d = cfg["num_hidden_layers"], cfg["num_key_value_heads"], cfg["head_dim"]
    return L * 2 * kvh * d + L * 2 * kvh * 4


def decode_step_bytes(cfg: Dict[str, Any], rows: float, live_tokens: float) -> float:
    """HBM bytes one decode step of ``rows`` sequences must move when
    ``live_tokens`` tokens of context are cached in all: the weights
    once, the live KV once, one embedding row and one new KV entry per
    sequence."""
    per_row = 2 * cfg["hidden_size"] + kv_bytes_per_token(cfg)
    return decode_weight_bytes(cfg) + live_tokens * kv_bytes_per_token(cfg) + rows * per_row


def decode_step_flops(cfg: Dict[str, Any], rows: float, live_tokens: float) -> float:
    """Multiply-adds x 2 of one decode step: every matrix once per row,
    and attention's two products over the live context."""
    matrices = cfg["num_hidden_layers"] * layer_weight_elements(cfg) + cfg["hidden_size"] * cfg["vocab_size"]
    attn = 2 * cfg["num_hidden_layers"] * cfg["num_attention_heads"] * cfg["head_dim"] * live_tokens
    return 2.0 * (rows * matrices + attn)


def decode_step_floor_s(cfg: Dict[str, Any], peaks: Dict[str, float], rows: float, mean_context: float) -> float:
    """The larger of bytes over peak bandwidth and operations over the
    int8 peak, for ``rows`` x ``mean_context`` live tokens."""
    live = rows * mean_context
    t_bytes = decode_step_bytes(cfg, rows, live) / peaks["hbm_bytes_per_s"]
    t_flops = decode_step_flops(cfg, rows, live) / peaks["int8_ops_per_s"]
    return max(t_bytes, t_flops)
