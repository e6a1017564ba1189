"""An adapter that lives in the TEST tree: proof of the harness's seams.

The program has one decoder, so this registers the same tiny decoder
shapes under ANOTHER name through code of its own and brings everything
else an adapter owes (``perfbench/arch/__init__.py``) written
differently from ``perfbench/arch/mistral.py``: a float64 numpy
reference that walks the positions one by one (rotary as a complex
rotation, attention as a loop over query positions and heads), its own
count of a decode step's bytes, and a reader of its own that a metric
file names as ``tests.perfbench.other_arch:decode_step_bytes_mean``. No
file of ``perfbench/`` names this module. It proves the plumbing, not an
architecture.

TOLERANCE 0.04: the same engine arithmetic as the Mistral adapter's
debug-tiny runs (bfloat16 activations against a float reference on the
same integers; 0.0075 and 0.0138 read at this size).
"""
from __future__ import annotations

from typing import Any, Dict, List, Sequence

import numpy as np

TOLERANCE = 0.04


def register(cfg: Dict[str, Any]) -> None:
    from generativeaiexamples_tpu.models import llama

    llama.PRESETS[cfg["name"]] = llama.LlamaConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"], num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"], num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]), max_seq_len=cfg["max_position_embeddings"],
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
    )


def engine_prefill_logits(eng, prompts, on_tpu: bool):
    """One prompt at a time through the engine's prefill forward."""
    import jax
    import jax.numpy as jnp

    from generativeaiexamples_tpu.models import llama
    from generativeaiexamples_tpu.parallel.mesh import mesh_context

    def fwd(params, tokens, lens):
        return llama.prefill_layers(params, eng.model_config, tokens, lens, use_flash=False,
                                    quant_kernel=eng._quant_kernel, tp=eng._tp, interpret=not on_tpu)[0]

    out = []
    with mesh_context(eng._mesh):
        for p in prompts:
            tok = np.zeros((1, 128), np.int32)
            tok[0, : len(p)] = p
            out.append(np.asarray(jax.jit(fwd)(eng.params, jnp.asarray(tok), jnp.asarray([len(p)], jnp.int32)),
                                  np.float32)[0])
    return np.stack(out)


def _matrix(pack, k: int, f: int, lo: int = 0) -> np.ndarray:
    """Columns [lo, lo + f) of an unsharded int8 pack as float64."""
    q = np.asarray(pack["q"])[:k, lo:lo + f].astype(np.float64)
    return q * np.asarray(pack["scale"], np.float64).reshape(1, -1)[:, lo:lo + f]


def _norm(x: np.ndarray, w: np.ndarray, eps: float) -> np.ndarray:
    return x / np.sqrt((x * x).mean(-1, keepdims=True) + eps) * w


def _turn(x: np.ndarray, theta: float) -> np.ndarray:
    """Rotary embedding of x [T, heads, d] as a complex rotation of the
    pairs (i, i + d/2)."""
    half = x.shape[-1] // 2
    angle = np.arange(x.shape[0])[:, None] * theta ** (-np.arange(half) / half)[None, :]
    z = (x[..., :half] + 1j * x[..., half:]) * np.exp(1j * angle)[:, None, :]
    return np.concatenate([z.real, z.imag], axis=-1)


def reference_sequence(tokens: Sequence[int], cfg: Dict[str, Any], embed, layers: List[Dict[str, np.ndarray]],
                       final_norm, head) -> np.ndarray:
    """Logits [T, vocab] of one sequence, position by position."""
    nh, nkv, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    eps, theta, T = cfg["rms_norm_eps"], cfg["rope_theta"], len(tokens)
    h = np.asarray(embed, np.float64)[np.asarray(tokens)]
    for w in layers:
        x = _norm(h, w["attn_norm"], eps)
        q = _turn((x @ w["wq"]).reshape(T, nh, d), theta)
        k = _turn((x @ w["wk"]).reshape(T, nkv, d), theta)
        v = (x @ w["wv"]).reshape(T, nkv, d)
        attn = np.zeros((T, nh, d))
        for t in range(T):
            for head_i in range(nh):
                kv = head_i // (nh // nkv)
                s = k[: t + 1, kv] @ q[t, head_i] / np.sqrt(d)
                p = np.exp(s - s.max())
                attn[t, head_i] = (p / p.sum()) @ v[: t + 1, kv]
        h = h + attn.reshape(T, nh * d) @ w["wo"]
        x = _norm(h, w["mlp_norm"], eps)
        gate = x @ w["w_gate"]
        h = h + (gate / (1.0 + np.exp(-gate)) * (x @ w["w_up"])) @ w["w_down"]
    return (_norm(h, np.asarray(final_norm, np.float64), eps) @ head).astype(np.float32)


def reference_logits(eng, cfg: Dict[str, Any], sequences, tp: int = 1, device=None) -> List[np.ndarray]:
    if tp != 1:
        raise ValueError("the test-tree adapter reads unsharded packs only")
    params = eng.params
    hid, m = cfg["hidden_size"], cfg["intermediate_size"]
    qd, kvd = cfg["num_attention_heads"] * cfg["head_dim"], cfg["num_key_value_heads"] * cfg["head_dim"]
    stacked = params["layers"]
    layers = []
    for i in range(cfg["num_hidden_layers"]):
        lp = stacked[i] if isinstance(stacked, (list, tuple)) else {
            k: ({a: b[i] for a, b in v.items()} if isinstance(v, dict) else v[i]) for k, v in stacked.items()}
        layers.append({
            "attn_norm": np.asarray(lp["attn_norm"], np.float64), "mlp_norm": np.asarray(lp["mlp_norm"], np.float64),
            "wq": _matrix(lp["wqkv"], hid, qd), "wk": _matrix(lp["wqkv"], hid, kvd, qd),
            "wv": _matrix(lp["wqkv"], hid, kvd, qd + kvd), "wo": _matrix(lp["wo"], qd, hid),
            "w_gate": _matrix(lp["w_gateup"], hid, m), "w_up": _matrix(lp["w_gateup"], hid, m, m),
            "w_down": _matrix(lp["w_down"], m, hid),
        })
    head = _matrix(params["lm_head"], hid, cfg["vocab_size"])
    return [reference_sequence(s, cfg, params["embed"], layers, params["final_norm"], head) for s in sequences]


def decode_step_bytes(cfg: Dict[str, Any], rows: float, mean_context: float) -> float:
    """This adapter's own count: one byte a matrix element, the K and V
    of every cached token once, nothing else."""
    hid, m, L = cfg["hidden_size"], cfg["intermediate_size"], cfg["num_hidden_layers"]
    qd, kvd = cfg["num_attention_heads"] * cfg["head_dim"], cfg["num_key_value_heads"] * cfg["head_dim"]
    weights = L * (hid * (qd + 2 * kvd) + qd * hid + 3 * hid * m) + hid * cfg["vocab_size"]
    return weights + rows * mean_context * L * 2 * kvd


def decode_step_floor_s(cfg: Dict[str, Any], peaks: Dict[str, float], rows: float, mean_context: float) -> float:
    return decode_step_bytes(cfg, rows, mean_context) / peaks["hbm_bytes_per_s"]


def decode_step_bytes_mean(ctx, params):
    """A reader of this module's own: the adapter's byte count for the
    window's mean decode rows and mean context (a count, so a CPU run
    reads it too)."""
    from perfbench import readers

    rows = readers.span_mean(ctx, {"kind": "decode", "field": "rows"})
    context = readers.mean_decode_context(ctx)
    if rows is None or context is None:
        return None
    return params.get("scale", 1.0) * decode_step_bytes(ctx["config"], rows, context)
