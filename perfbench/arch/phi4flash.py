"""The adapter of Phi-4-mini-flash-reasoning (contract: ``perfbench/arch/__init__.py``).

**Registration.** ``register`` writes the configuration file's published
sizes into the program's model registry under the configuration's name,
as a configuration of the ``phi4flash`` family.

**The plain reference**: the SambaY decoder-hybrid-decoder in float32
``jax.numpy``, written from the layer equations of ISSUE 29 / arXiv
2507.06607, every layer at every position: no prefill shortcut, no
cache, no kernel, no pair layout (the two softmaxes of a differential
head are computed as two attentions over heads of 64), importing nothing
of the program. It reads the engine's OWN bfloat16 weights, layer by
layer, and widens them to float32. With ``n`` layers, layer ``l``:
even and ``<= n/2`` Mamba-1 (layer ``n/2`` publishes its pre-gate scan
output as the memory); odd and ``< n/2`` differential attention over a
window of ``sliding_window`` keys including the query's own; ``n/2 + 1``
the same, full causal; odd above it differential cross attention onto
layer ``n/2 + 1``'s keys and values; even above it the gated memory
unit. LayerNorm with bias before each mixer and each SwiGLU MLP, a tied
output head, no positional encoding.

TOLERANCE, as max|engine - reference| / max|reference| over a prompt's
last-position logits (and, for the served tokens, the reference's
margin between its own maximum and the engine's token): the engine
computes in bfloat16 (relative rounding 2^-8 = 0.0039) through 32 layers
of two to four matrix products each; independent roundings add as a
random walk: 0.0039 x sqrt(3 x 32) = 0.038 is where roundings alone
could take it. The two readings the limit sits between (PERF.md
section 6, PR 29): the engine against this reference on the chip at the
published widths, the largest over every run and seed, and this
reference computed one precision down (every matrix product and
activation in bfloat16, ``reference_logits(..., precision="bfloat16")``),
which has to come out as NOT correct. Both are written beside
``TOLERANCE`` below.

**Bytes and operations a decode step needs**, from the configuration's
shapes, kept with the benchmark so that no PR which claims a gain can
change the count. The weights are bfloat16, so operations are held
against the bfloat16 peak.
"""
from __future__ import annotations

import contextlib
import math
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

# The two readings (PERF.md section 6, PR 29), on the prompts of 64, 96 and 640
# tokens the launcher compares, at the published widths, all 32 layers:
# - the engine's SERVED walks (``engine_prefill_logits`` below) on the chip (one TPU
#   v5 lite) against this float32 reference: 0.0313 (prefill), 0.0243 (prefill and
#   one decode step through the compiled page kernel), 0.0217 (two extend chunks) in
#   all seven runs of the committed tree (prompts and weights are fixed, so the
#   numbers repeat to the digit); the served tokens' margin 0.0014 at most over 24
#   tokens. (The same walks on the host CPU, the kernel interpreted: 0.0295, 0.0287,
#   0.0246. The cache-free forward the first runs of PR 29 compared read 0.0291.)
# - the control one precision down (``precision="bfloat16"``: nothing in
#   float32) against the same reference: 0.0781, 0.0726, 0.0662 (host CPU).
# 0.045 lies between, a factor of 1.44 above the largest of the first and 1.47
# below the smallest of the second.
TOLERANCE = 0.045


# --------------------------------------------------------------------------- #
# The engine's side: registration and the logits of its served walks


def model_config(cfg: dict):
    from generativeaiexamples_tpu.models.phi4flash import Phi4FlashConfig

    return Phi4FlashConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"], num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"], num_kv_heads=cfg["num_key_value_heads"],
        sliding_window=cfg["sliding_window"], norm_eps=float(cfg["layer_norm_eps"]),
        max_seq_len=cfg["max_position_embeddings"], tie_embeddings=bool(cfg["tie_word_embeddings"]),
        d_state=cfg["mamba_d_state"], d_conv=cfg["mamba_d_conv"], expand=cfg["mamba_expand"],
    )


def register(cfg: dict) -> None:
    from generativeaiexamples_tpu.models import registry

    registry.register_preset("phi4flash", cfg["name"], model_config(cfg))


def engine_prefill_logits(eng, prompts, on_tpu: bool):
    """Last-prompt-position logits from the walks the engine SERVES with:
    its family's ``prefill_paged``, ``extend_paged``, ``decode_paged`` and
    ``head`` (the functions its step programs jit), on the engine's
    weights, in the engine's shapes for one row (a ``prefill_chunk`` of
    tokens, a window rung of its ladder, the page kernel as the engine
    resolved it), over a scratch cache of ONE slot that goes
    from prompt to prompt as the last one left it, so every admission
    has a former tenant's state to reset. By prompt:

    - longer than ``prefill_chunk``: chunked extend, the state carried
      from chunk to chunk in the rings, the scan state, the convolution
      tail and the pages, then the head;
    - the first of the others: the monolithic prefill program alone;
    - every other one: prefill of all but its last token, then ONE decode
      step on that token (ring and page writes read back, the single-step
      state update, the page kernel over the pair layout).
    """
    import jax
    import jax.numpy as jnp

    del on_tpu  # the engine resolved its page kernel for the platform it runs on
    fam, cfg, params = eng._family, eng.model_config, eng.params
    C, page = eng.engine_config.prefill_chunk, eng.engine_config.page_size
    pmax = max(1, eng._attention_window(max(len(p) for p in prompts)) // page)
    tables = jnp.asarray(1 + np.arange(pmax, dtype=np.int32)[None, :])  # page 0 is the scratch page
    caches = fam.init_paged_cache(cfg, 1 + pmax, page, 1, eng._cache["pool"]["k"].dtype)
    slot = jnp.zeros((1,), jnp.int32)
    one = lambda n: jnp.asarray([n], jnp.int32)  # noqa: E731

    # (called once a run; every shape is the engine's own for one row)
    prefill = jax.jit(lambda params, caches, tok, n: fam.prefill_paged(params, cfg, caches, tok, n, slot, tables, page))
    extend = jax.jit(
        lambda params, caches, tok, off, n, window: fam.extend_paged(
            params, cfg, caches, tok, off, n, slot, tables, window, page),
        static_argnums=5)
    decode = jax.jit(
        lambda params, caches, tok, pos, window: fam.decode_paged(
            params, cfg, caches, tok, pos, jnp.ones((1,), bool), tables, window, page,
            page_kernel=eng._paged_kernel),
        static_argnums=4)
    head = jax.jit(lambda params, hidden: fam.head(params, cfg, hidden))

    def chunk(tokens):
        row = np.zeros((1, C), np.int32)
        row[0, : len(tokens)] = tokens
        return jnp.asarray(row)

    out, prefilled_alone = [], False
    for p in prompts:
        n = one(len(p))
        if len(p) > C:
            window = eng._attention_window(len(p))  # the last chunk's rung for every chunk: one program
            for k in range(0, len(p), C):
                valid = jnp.minimum(n - k, C)
                # genai-lint: disable=shape-cardinality -- offsets and lengths as [1] values; the window is a rung of the engine's ladder
                hidden, caches = extend(params, caches, chunk(p[k:k + C]), one(k), valid, window)
            logits = head(params, hidden)  # genai-lint: disable=shape-cardinality -- hidden is [1, D] whatever the prompt
        elif not prefilled_alone or len(p) < 2:
            prefilled_alone = True
            logits, caches = prefill(params, caches, chunk(p), n)  # genai-lint: disable=shape-cardinality -- a length as a [1] value
        else:
            _, caches = prefill(params, caches, chunk(p[:-1]), n - 1)  # genai-lint: disable=shape-cardinality -- a length as a [1] value
            window = eng._attention_window(len(p))  # a rung of the engine's ladder
            logits, caches = decode(params, caches, one(p[-1]), n - 1, window)  # genai-lint: disable=shape-cardinality -- a position as a [1] value
        out.append(np.asarray(logits, np.float32)[0])
    return out


# --------------------------------------------------------------------------- #
# The plain float32 reference (imports nothing of the program)


def layer_kind(l: int, n: int) -> str:
    if l % 2 == 0:
        return "mamba" if l <= n // 2 else "gmu"
    if l < n // 2:
        return "window"
    return "full" if l == n // 2 + 1 else "cross"


def _ln(x, w, b, eps):
    import jax.numpy as jnp

    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * w + b


def _silu(x):
    import jax

    return x * jax.nn.sigmoid(x)


def mamba_mixer(x, w: Dict[str, Any], cfg: Dict[str, Any]):
    """x [T, hidden] -> (mixer output [T, hidden], scan output y [T, d_inner])."""
    import jax
    import jax.numpy as jnp

    T = x.shape[0]
    ds, dc = cfg["mamba_d_state"], cfg["mamba_d_conv"]
    r = -(-cfg["hidden_size"] // 16)
    xz = x @ w["in_proj"]
    xin, z = xz[:, : xz.shape[1] // 2], xz[:, xz.shape[1] // 2:]
    padded = jnp.concatenate([jnp.zeros((dc - 1, xin.shape[1]), xin.dtype), xin], axis=0)
    conv = sum(padded[k:k + T] * w["conv_w"][k] for k in range(dc)) + w["conv_b"]
    u = _silu(conv)
    dbc = u @ w["x_proj"]
    d, Bm, Cm = dbc[:, :r], dbc[:, r:r + ds], dbc[:, r + ds:]
    dt = jax.nn.softplus(d @ w["dt_proj"] + w["dt_bias"])  # [T, d_inner]
    A = -jnp.exp(w["A_log"])  # [d_state, d_inner]

    def step(s, inp):
        u_t, dt_t, b_t, c_t = inp
        s = jnp.exp(dt_t[None, :] * A) * s + (dt_t * u_t)[None, :] * b_t[:, None]
        return s, jnp.sum(s * c_t[:, None], axis=0)

    _, ys = jax.lax.scan(step, jnp.zeros(A.shape, A.dtype), (u, dt, Bm, Cm))
    y = ys + w["D"] * u
    return (y * _silu(z)) @ w["out_proj"], y


def lambda_init(l: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * l)


def diff_attention(q, k, v, w: Dict[str, Any], cfg: Dict[str, Any], lam0, mask):
    """q [T, Hq, 64], k/v [S, Hkv, 64], mask [T, S] -> [T, hidden].
    Diff-head i: queries (2i, 2i+1); diff-KV-head j = i // 2: keys and
    values (2j, 2j+1); both softmaxes multiply [v1|v2]. ``lam0`` is the
    layer's 0.8 - 0.6 exp(-0.3 l)."""
    import jax
    import jax.numpy as jnp

    T, Hq, Dh = q.shape
    S, Hkv = k.shape[0], k.shape[1]
    group = (Hq // 2) // (Hkv // 2)
    lam = jnp.exp(jnp.sum(w["lq1"] * w["lk1"])) - jnp.exp(jnp.sum(w["lq2"] * w["lk2"])) + lam0
    q = q.reshape(T, Hq // 2, 2, Dh)  # [T, diff-head, half, Dh]
    k = jnp.repeat(k.reshape(S, Hkv // 2, 2, Dh), group, axis=1)  # diff-head i reads diff-KV-head i // group
    vcat = jnp.repeat(v.reshape(S, Hkv // 2, 2 * Dh), group, axis=1)  # [S, diff-head, 2 Dh]
    sc = jnp.einsum("tihd,sihd->ihts", q, k) / math.sqrt(Dh)
    p = jax.nn.softmax(jnp.where(mask[None, None], sc, -jnp.inf), axis=-1)
    a = jnp.einsum("ihts,sie->tihe", p, vcat)  # [T, diff-head, half, 2 Dh]
    d = a[:, :, 0] - lam * a[:, :, 1]
    d = d / jnp.sqrt(jnp.mean(d * d, axis=-1, keepdims=True) + cfg["layer_norm_eps"]) * w["subln"]
    return ((1.0 - lam0) * d).reshape(T, -1) @ w["wo"] + w["bo"]


def memory_layer(n: int) -> int:
    """The Mamba layer whose scan output the gated memory units read."""
    return n // 2


def layer_forward(h, w: Dict[str, Any], cfg: Dict[str, Any], l: int, carry: Dict[str, Any], lam0=None):
    """One layer on one sequence h [T, hidden], float32. ``carry`` holds
    what later layers read: the memory (layer n/2) and the full layer's
    keys and values. ``l`` decides the layer's kind and whether it
    publishes the memory; ``lam0`` may be handed in (so that layers of
    one kind share one compiled step)."""
    import jax.numpy as jnp

    n, T = cfg["num_hidden_layers"], h.shape[0]
    Hq, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    Dh = cfg["hidden_size"] // Hq
    kind = layer_kind(l, n)
    lam0 = lambda_init(l) if lam0 is None else lam0
    x = _ln(h, w["ln1_w"], w["ln1_b"], cfg["layer_norm_eps"])
    pos = jnp.arange(T)
    causal = pos[:, None] >= pos[None, :]
    if kind == "mamba":
        out, y = mamba_mixer(x, w, cfg)
        if l == memory_layer(n):
            carry = dict(carry, memory=y)
    elif kind in ("window", "full"):
        qkv = x @ w["wqkv"] + w["bqkv"]
        q = qkv[:, : Hq * Dh].reshape(T, Hq, Dh)
        k = qkv[:, Hq * Dh: (Hq + Hkv) * Dh].reshape(T, Hkv, Dh)
        v = qkv[:, (Hq + Hkv) * Dh:].reshape(T, Hkv, Dh)
        mask = causal
        if kind == "window":  # t attends t - window + 1 .. t
            mask = causal & (pos[None, :] > pos[:, None] - cfg["sliding_window"])
        else:
            carry = dict(carry, k=k, v=v)
        out = diff_attention(q, k, v, w, cfg, lam0, mask)
    elif kind == "cross":
        q = (x @ w["wq"] + w["bq"]).reshape(T, Hq, Dh)
        out = diff_attention(q, carry["k"], carry["v"], w, cfg, lam0, causal)
    else:
        out = (carry["memory"] * _silu(x @ w["w1"])) @ w["w2"]
    h = h + out
    x = _ln(h, w["ln2_w"], w["ln2_b"], cfg["layer_norm_eps"])
    gu = x @ w["w_gate_up"]
    m = gu.shape[1] // 2
    return h + (_silu(gu[:, :m]) * gu[:, m:]) @ w["w_down"], carry


def forward(tokens_list: Sequence[Sequence[int]], cfg: Dict[str, Any], embed, layer_weights,
            final_norm, device=None, precision: str = "float32") -> List[np.ndarray]:
    """Logits [T, vocab] of every position of every sequence. Each layer's
    weights are fetched once (``layer_weights(l)``), applied to all
    sequences, then dropped. ``precision="bfloat16"`` is the control one
    precision down: NOTHING in float32 — the configuration serves
    bfloat16 matrices with a float32 residual stream, scan state,
    normalisation and softmax; the control rounds those to bfloat16 too."""
    import jax
    import jax.numpy as jnp

    ctx = jax.default_device(device) if device is not None else contextlib.nullcontext()
    dt = jnp.float32 if precision == "float32" else jnp.bfloat16
    n = cfg["num_hidden_layers"]
    with ctx, jax.default_matmul_precision("highest"):
        emb = jnp.asarray(np.asarray(embed, np.float32)).astype(dt)
        hs = [embed_tokens(emb, t) for t in tokens_list]
        carries: List[Dict[str, Any]] = [{} for _ in tokens_list]
        steps: Dict[Any, Any] = {}  # layers of one kind share one compiled step
        for l in range(n):
            w = {k: jnp.asarray(v).astype(dt) for k, v in layer_weights(l).items()}
            key = (layer_kind(l, n), l == memory_layer(n))
            if key not in steps:
                steps[key] = jax.jit(
                    lambda h, w, carry, lam0, l=l: _cast(layer_forward(h, w, cfg, l, carry, lam0), dt))
            for i, h in enumerate(hs):
                hs[i], carries[i] = steps[key](h, w, carries[i], jnp.asarray(lambda_init(l), dt))
                hs[i].block_until_ready()
            del w
        fw, fb = (jnp.asarray(np.asarray(x, np.float32)).astype(dt) for x in final_norm)
        return [np.asarray((_ln(h, fw, fb, cfg["layer_norm_eps"]) @ emb.T).astype(jnp.float32)) for h in hs]


def embed_tokens(emb, tokens):
    """The input of layer 0: the embedding rows, and nothing of the positions."""
    return emb[np.asarray(tokens)]


def _cast(out, dt):
    import jax

    return jax.tree.map(lambda x: x.astype(dt), out)


def engine_layer_weights(params: Dict[str, Any], l: int) -> Dict[str, np.ndarray]:
    """Layer ``l`` of the engine's parameter tree as float32 arrays."""
    import jax

    return {k: np.asarray(jax.device_get(v)).astype(np.float32) for k, v in params["layers"][l].items()}


def reference_logits(eng, cfg: Dict[str, Any], sequences: Sequence[Sequence[int]], tp: int = 1,
                     device=None, precision: str = "float32") -> List[np.ndarray]:
    """``forward`` over the engine's own parameter tree."""
    import jax

    del tp  # one device serves this model
    params = eng.params
    host = lambda x: np.asarray(jax.device_get(x)).astype(np.float32)  # noqa: E731
    return forward(
        sequences, cfg, host(params["embed"]), lambda l: engine_layer_weights(params, l),
        (host(params["final_norm_w"]), host(params["final_norm_b"])), device=device, precision=precision,
    )


# --------------------------------------------------------------------------- #
# Bytes and operations of a decode step


def _sizes(cfg: Dict[str, Any]) -> Dict[str, int]:
    h, n = cfg["hidden_size"], cfg["num_hidden_layers"]
    di = cfg["mamba_expand"] * h
    kinds = [layer_kind(l, n) for l in range(n)]
    return {
        "h": h, "m": cfg["intermediate_size"], "di": di, "ds": cfg["mamba_d_state"],
        "dc": cfg["mamba_d_conv"], "r": -(-h // 16), "q": h,
        "kv": cfg["num_key_value_heads"] * (h // cfg["num_attention_heads"]),
        "n_mamba": kinds.count("mamba"), "n_window": kinds.count("window"),
        "n_cross": kinds.count("cross"), "n_gmu": kinds.count("gmu"), "n": n,
    }


def matrix_elements(cfg: Dict[str, Any]) -> int:
    """Matrix elements one token multiplies: every layer's mixer and
    MLP, and the tied head (the embedding table, read whole)."""
    s = _sizes(cfg)
    mamba = s["h"] * 2 * s["di"] + s["di"] * (s["r"] + 2 * s["ds"]) + s["r"] * s["di"] + s["di"] * s["h"]
    attn = s["h"] * (s["q"] + 2 * s["kv"]) + s["q"] * s["h"]
    cross = 2 * s["h"] * s["q"]
    gmu = 2 * s["h"] * s["di"]
    mlp = 3 * s["h"] * s["m"]
    return (s["n_mamba"] * mamba + (s["n_window"] + 1) * attn + s["n_cross"] * cross
            + s["n_gmu"] * gmu + s["n"] * mlp + cfg["vocab_size"] * s["h"])


def decode_weight_bytes(cfg: Dict[str, Any]) -> int:
    """bfloat16 matrices (2 bytes an element) plus the vectors: norms,
    biases, the convolution, and the scan's float32 parameters."""
    s = _sizes(cfg)
    vectors = 2 * (s["n"] * 4 * s["h"] + 2 * s["h"]
                   + s["n_mamba"] * (s["dc"] + 1) * s["di"]
                   + (s["n_window"] + 1) * (s["q"] + 2 * s["kv"] + s["h"])
                   + s["n_cross"] * (s["q"] + s["h"]))
    scan = 4 * s["n_mamba"] * (s["di"] * s["ds"] + 2 * s["di"])
    return 2 * matrix_elements(cfg) + vectors + scan


def kv_bytes_per_token(cfg: Dict[str, Any]) -> int:
    """bfloat16 K and V of ONE layer."""
    return 2 * 2 * _sizes(cfg)["kv"]


def state_bytes_per_row(cfg: Dict[str, Any]) -> int:
    """The recurrent state a decode step reads AND writes per row: the
    float32 SSM states and the bfloat16 convolution tails."""
    s = _sizes(cfg)
    return 2 * s["n_mamba"] * (4 * s["di"] * s["ds"] + 2 * (s["dc"] - 1) * s["di"])


def decode_step_bytes(cfg: Dict[str, Any], rows: float, mean_context: float) -> float:
    """HBM bytes one decode step of ``rows`` sequences must move: the
    weights once; per row the shared K/V once for each of its readers
    (the full layer and every cross layer: nothing keeps it on chip
    between them), a window of each window layer, the recurrent state
    in and out, one embedding row and the new K/V entries."""
    s = _sizes(cfg)
    per_tok = kv_bytes_per_token(cfg)
    readers = 1 + s["n_cross"]
    per_row = (readers * mean_context * per_tok
               + s["n_window"] * min(mean_context, cfg["sliding_window"]) * per_tok
               + state_bytes_per_row(cfg) + 2 * s["h"] + (1 + s["n_window"]) * per_tok)
    return decode_weight_bytes(cfg) + rows * per_row


def decode_step_flops(cfg: Dict[str, Any], rows: float, mean_context: float) -> float:
    """Multiply-adds x 2: every matrix once per row, attention's two
    products over the context (shared K/V readers) and the windows."""
    s = _sizes(cfg)
    attn = 2 * s["q"] * ((1 + s["n_cross"]) * mean_context
                         + s["n_window"] * min(mean_context, cfg["sliding_window"])) * 2
    return 2.0 * rows * (matrix_elements(cfg) + attn)


def decode_step_floor_s(cfg: Dict[str, Any], peaks: Dict[str, float], rows: float, mean_context: float) -> float:
    t_bytes = decode_step_bytes(cfg, rows, mean_context) / peaks["hbm_bytes_per_s"]
    t_flops = decode_step_flops(cfg, rows, mean_context) / peaks["bf16_flops_per_s"]
    return max(t_bytes, t_flops)


# --------------------------------------------------------------------------- #
# Readers of this architecture's own counters


def prefill_cross_skipped_share(ctx, params) -> Optional[float]:
    """Prompt tokens the upper half never saw over prompt tokens
    prefilled, between the two ``/metrics`` scrapes, percent. A program
    without the two counters (the parent) gives nothing to read."""
    from perfbench import readers

    def grew(name: str) -> Optional[float]:
        if not any(n == name for n, _ in ctx["metrics_after"]):
            return None
        return readers.metric_sum(ctx["metrics_after"], name) - readers.metric_sum(ctx["metrics_before"], name)

    skipped = grew("genai_engine_prefill_cross_skipped_tokens_total")
    prefilled = grew("genai_engine_prefill_tokens_total")
    if skipped is None or not prefilled:
        return None
    return 100.0 * skipped / prefilled
