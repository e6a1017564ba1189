"""The plain reference: a Mistral/Llama-style decoder in float32 jax.numpy.

Written from the published description of the architecture (pre-norm
residual blocks; RMSNorm; rotary position embedding applied to halves of
each head, ``rotate_half`` as in the model's public implementation;
grouped-query attention with a causal mask; SwiGLU; an untied output
head). It uses no function of the program's ``ops/``, ``engine/`` or
``models/``: no kernel, no cache, no batching — the whole sequence is
recomputed, every position's logits come from one pass.

``compare`` is what decides ``correct``: the engine's prefill logits and
its greedy decode through the cache against this forward pass on the
engine's OWN int8 weights, dequantised to float32 layer by layer (the
reference never holds more than one layer in float32).

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
"""
from __future__ import annotations

from typing import Any, Callable, Dict, List, Sequence

import numpy as np

TOLERANCE = 0.04


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


def _dense(w):
    """A weight as float32: given either as an array or as an int8 matrix
    with its float32 scale per output channel, ``(q, scale)``."""
    import jax.numpy as jnp

    if isinstance(w, (tuple, list)):
        q, scale = w
        return q.astype(jnp.float32) * scale.astype(jnp.float32)
    return w.astype(jnp.float32)


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

    ctx = jax.default_device(device) if device is not None else _null()
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


class _null:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


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


def seeded_prompts(lengths: Sequence[int], vocab: int, seed: int) -> List[List[int]]:
    rng = np.random.default_rng(seed)
    return [[int(t) for t in rng.integers(0, vocab, size=n)] for n in lengths]


def compare(prompts: Sequence[Sequence[int]], engine_logits: Sequence[np.ndarray],
            engine_tokens: Sequence[Sequence[int]], ref_logits: Sequence[np.ndarray],
            tolerance: float = TOLERANCE) -> Dict[str, Any]:
    """``ref_logits[i]`` covers prompt i followed by the engine's tokens.
    (i) last-prompt-position logits: max|diff|/max|ref| (a prompt whose
    ``engine_logits`` entry is None is served only and skips this);
    (ii) every engine token's reference logit lies within the same
    margin of the reference's maximum at its position (greedy decode
    through the cache chose a token the reference also ranks at the top,
    up to rounding). Every prompt must have produced a token: a served
    path that delivers nothing does not pass."""
    prefill_err, decode_margin = [], []
    for prompt, eng, toks, ref in zip(prompts, engine_logits, engine_tokens, ref_logits):
        T = len(prompt)
        if eng is not None:
            last = ref[T - 1]
            scale = max(float(np.max(np.abs(last))), 1e-6)
            eng = np.asarray(eng, np.float32)[: last.shape[0]]
            prefill_err.append(float(np.max(np.abs(eng - last)) / scale) if np.all(np.isfinite(eng)) else float("inf"))
        for j, tok in enumerate(toks):
            row = ref[T - 1 + j]
            s = max(float(np.max(np.abs(row))), 1e-6)
            decode_margin.append(float((np.max(row) - row[tok]) / s))
    ok = (
        bool(prefill_err) and max(prefill_err) <= tolerance
        and all(len(t) > 0 for t in engine_tokens)
        and bool(decode_margin) and max(decode_margin) <= tolerance
    )
    return {
        "ok": bool(ok), "tolerance": tolerance,
        "prefill_rel_err": prefill_err,
        "decode_margin_max": max(decode_margin) if decode_margin else None,
        "decode_tokens_checked": len(decode_margin),
    }
