"""The adapter of Trinity-Mini (``afmoe``, 26B-A3B) as one pipeline
stage's chip (contract: ``perfbench/arch/__init__.py``).

**Registration.** ``register`` writes the configuration file's published
widths, the layers served and the experts held (all 128) into the
program's model registry under the configuration's name, as a
configuration of the ``afmoe`` family.

**The plain reference**: float32 ``jax.numpy`` written from the layer
equations of ISSUE 42 (Hugging Face ``modeling_afmoe.py`` as published
with the model), importing nothing of the program: no kernel, no cache,
no ring, no batching; whole-sequence masks. It reads the engine's OWN
bfloat16 weights, layer by layer, and widens them to float32. The
engine holds the four attention projections as one matrix ``wqkvg``
``[q | k | v | gate]``; the reference splits it. Per layer, with
``x [T, D]`` the residual rows of one sequence and
``N(u) = u / sqrt(mean(u^2) + 1e-5) w``:

- ``x0 = E[token] sqrt(2048)`` (``mup_enabled``).
- ``h = x + N2(Attn(N1(x)))``: ``q = N_q(W_q u)``, ``k = N_k(W_k u)`` per
  head over 128, ``v = W_v u``, ``g = W_g u``; on a ``sliding_attention``
  layer q and k are rotated (rotate-half RoPE over all 128, theta 10000)
  and a query at t sees keys t-2047..t; on a ``full_attention`` layer
  nothing is rotated and every key 0..t is seen; scores scaled by
  128^-0.5, softmax in float32, KV head j serves query heads 8j..8j+7;
  ``Attn = W_o [softmax(q k^T) v sigmoid(g)]``.
- ``x' = h + N4(MLP(N3(h)))``: layers below ``num_dense_layers`` a SwiGLU
  of 6144; the others ``s = sigmoid(W_r h)``, ``T = top8(s + b)``,
  ``g_e = 2.826 s_e / sum_T s``, output ``Shared(h) + sum_T g_e E_e(h)``,
  every expert a SwiGLU of 1024 with no clamp, computed by a loop over
  the routed pairs (expert by expert over the tokens routed to it). The
  router and the expert loop are the functions of
  ``perfbench/arch/glm5next.py`` (the same equations under the same
  keys). DEPARTURE: the published denominator is ``sum_T s + 1e-20``;
  the eight sigmoid scores sum to far more than float32 resolves 1e-20
  against, so it is left out here and in the program alike.
- ``logits = W_head N_f(x_L)``.

``TOLERANCE``, as max|engine - reference| / max|reference| over a
prompt's last-position logits and the served tokens' margin: the two
readings it sits between are written beside it below (PERF.md section 6,
PR 42).

**Bytes and operations** of a decode step (``decode_step_bytes``,
``decode_step_flops``) and of the grouped matmul (``expert_bytes``) are
counted here, so that no PR which claims a gain can change the count.
"""
from __future__ import annotations

import contextlib
import math
import threading
import time
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from perfbench.arch import glm5next as _shared  # the expert equations and the span helpers: the same under the same keys

# The two readings (PERF.md section 6, PR 42; my chip runs, one TPU v5 lite), prompts of
# 64, 96, 640 and 2,560 tokens, published widths, five layers, 128 experts held:
# - the engine's SERVED walks on the chip against this float32 reference, through the
#   compiled kernels: prefill_rel_err 0.0216 (one chunk), 0.0081 (95 tokens and one decode
#   step through the ring, the page kernel and the grouped matmul), 0.0103 (two extend
#   chunks), 0.0059 (five chunks: the ring wrapped and the window discarded keys); the
#   served tokens' margin 0.0585 over 32 tokens through the engine's own executables.
#   Prompts and weights are fixed, so the numbers repeat to the digit (three runs).
# - the control one precision down (``precision="bfloat16"``: nothing in float32, the
#   residual row, the norms, the softmax and the router included) against the same
#   reference, on the chip machine's host CPU with the CHIP's draws of the weights (the
#   engine's initialiser, seed 0, read back): prefill_rel_err 0.0368, 0.0303, 0.1589,
#   0.0149. It is NOT correct by prefill_rel_err, by that limit alone (its own tokens'
#   margin at those positions is 0.042 at most), and by ONE prompt, the 640-token one.
# 0.096 is the geometric mean of the served walks' largest reading (the margin, 0.0585)
# and the control's largest (0.1589): 1.65 above the one, 1.65 below the other.
# WHAT THE TWO LARGEST READINGS ARE: a router's top 8 that flipped, not rounding. With
# all 128 fine-grained experts here every flip counts: the float32 reference with ONLY
# the router's input rounded to bfloat16 reads 0.0012-0.020 at 28 of 36 positions and
# 0.096-0.189 at the other 8 (a ninth-ranked expert for an eighth-ranked one, gates of
# ~0.35 each, moves the layer's output by a third). Where no top 8 flips the served
# walks read 0.006-0.022 and the control 0.015-0.037: a factor of ~2 that this limit
# cannot see. A change of the served numerics can flip a compared position and fail the
# limit without being wrong (PERF.md section 7, Opened by PR 42 (a)).
TOLERANCE = 0.096

_PENDING: List[Any] = []  # the deferred walks of the last engine_prefill_logits call
Deferred = _shared.Deferred


# --------------------------------------------------------------------------- #
# The engine's side


def layer_kinds(cfg: dict) -> List[tuple]:
    """(sliding?, mlp) of each layer SERVED, from the published lists."""
    return [(cfg["layer_types"][l] == "sliding_attention", "dense" if l < cfg["num_dense_layers"] else "sparse")
            for l in cfg["layers_served"]]


def model_config(cfg: dict):
    from generativeaiexamples_tpu.models.afmoe import AfmoeConfig

    return AfmoeConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"], intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"], layer_types=tuple(cfg["layer_types"]),
        num_dense_layers=cfg["num_dense_layers"], layers_served=tuple(cfg["layers_served"]),
        n_routed_experts=cfg["num_experts"], num_experts_per_tok=cfg["num_experts_per_tok"],
        experts_first=cfg["experts_first"], experts_held=cfg["num_experts_held"],
        routed_scaling_factor=float(cfg["route_scale"]), num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"], sliding_window=cfg["sliding_window"],
        rope_theta=float(cfg["rope_theta"]), norm_eps=float(cfg["rms_norm_eps"]),
        mup_enabled=bool(cfg["mup_enabled"]), max_seq_len=cfg["engine"]["max_seq_len"],
    )


def register(cfg: dict) -> None:
    from generativeaiexamples_tpu.models import registry

    registry.register_preset("afmoe", cfg["name"], model_config(cfg))


def engine_prefill_logits(eng, prompts, on_tpu: bool):
    """Last-prompt-position logits from the walks the engine SERVES with
    (its family's ``extend_paged``, ``decode_paged`` and ``head``, with
    the kernel paths it resolved), on the engine's weights, in the
    engine's shapes for one row, over a scratch cache of ONE slot that
    goes from prompt to prompt as the last one left it, so every
    admission meets a former tenant's ring and pages. By prompt:

    - longer than ``prefill_chunk``: chunked extend (the rings and the
      pages carried from chunk to chunk; past ``sliding_window`` tokens
      the ring wraps and the window discards keys), then the head;
    - the first of the others: one chunk from position 0;
    - every other one: all but its last token the same way, then ONE
      decode step on that token (the ring read, the page kernel, the
      grouped matmul).

    The rows are ``Deferred``: the walks run when the first is read (the
    launcher's greedy requests enter the queue first)."""
    del on_tpu
    done: Dict[str, Any] = {}

    def compute():
        if "rows" not in done:
            done["rows"] = _served_logits(eng, [list(p) for p in prompts])
        return done["rows"]

    _PENDING[:] = [compute]
    return [Deferred(compute, i) for i in range(len(prompts))]


def _served_logits(eng, prompts) -> List[np.ndarray]:
    import jax
    import jax.numpy as jnp

    fam, cfg, params = eng._family, eng.model_config, eng.params
    C, page = eng.engine_config.prefill_chunk, eng.engine_config.page_size
    pmax = max(1, eng._attention_window(max(len(p) for p in prompts)) // page)
    tables = jnp.asarray(1 + np.arange(pmax, dtype=np.int32)[None, :])  # page 0 is the scratch page
    caches = fam.init_paged_cache(cfg, 1 + pmax, page, 1, eng._cache["full"][0]["k"].dtype)
    slot = jnp.zeros((1,), jnp.int32)
    one = lambda n: jnp.asarray([n], jnp.int32)  # noqa: E731
    paths = dict(eng._family_kernels)

    def extend_and_head(params, caches, tok, off, n):
        hidden, caches = fam.extend_paged(params, cfg, caches, tok, off, n, slot, tables, pmax * page, page, **paths)
        return fam.head(params, cfg, hidden), caches

    extend = jax.jit(extend_and_head)
    decode = jax.jit(lambda params, caches, tok, pos: fam.decode_paged(
        params, cfg, caches, tok, pos, jnp.ones((1,), bool), tables, pmax * page, page,
        page_kernel=eng._paged_kernel, **paths))

    def chunk(tokens):
        row = np.zeros((1, C), np.int32)
        row[0, : len(tokens)] = tokens
        return jnp.asarray(row)

    out, prefilled_alone = [], False
    for p in prompts:
        stepped = len(p) <= C and prefilled_alone and len(p) >= 2
        body = p[:-1] if stepped else p
        for k in range(0, len(body), C):
            # genai-lint: disable=shape-cardinality -- offsets and lengths as [1] values
            logits, caches = extend(params, caches, chunk(body[k:k + C]), one(k), one(min(C, len(body) - k)))
        if stepped:
            logits, caches = decode(params, caches, one(p[-1]), one(len(p) - 1))  # genai-lint: disable=shape-cardinality -- a position as a [1] value
        elif len(p) <= C:
            prefilled_alone = True
        out.append(np.asarray(logits, np.float32)[0])
    return out


# --------------------------------------------------------------------------- #
# The plain float32 reference (imports nothing of the program)


def rms(u, w, eps: float):
    import jax.numpy as jnp

    return u / jnp.sqrt(jnp.mean(u * u, axis=-1, keepdims=True) + eps) * w


def sublayer(x, w: Dict[str, Any], sub: str, cfg: Dict[str, Any], F):
    """``x + N_out(F(N_in(x)))``."""
    eps = cfg["rms_norm_eps"]
    return x + rms(F(rms(x, w[f"n_{sub}_in"], eps)), w[f"n_{sub}_out"], eps)


def rope_half(x, positions, theta: float):
    """Rotate-half RoPE over the whole last axis; x [T, H, Dh], positions [T]."""
    import jax.numpy as jnp

    half = x.shape[-1] // 2
    inv_freq = (theta ** (-np.arange(half, dtype=np.float64) / half)).astype(np.float32)
    ang = jnp.asarray(positions, jnp.float32)[:, None, None] * inv_freq[None, None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return (x * jnp.cos(ang) + rotated * jnp.sin(ang)).astype(x.dtype)


def attention(x, w: Dict[str, Any], cfg: Dict[str, Any], sliding: bool):
    """x [T, D] normed -> [T, D]: gated, QK-normed GQA over the whole sequence."""
    import jax
    import jax.numpy as jnp

    Hq, Hk, Dh = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    T = x.shape[0]
    pos = np.arange(T)
    q, k, v, g = jnp.split(x @ w["wqkvg"], [Hq * Dh, (Hq + Hk) * Dh, (Hq + 2 * Hk) * Dh], axis=1)
    q = rms(q.reshape(T, Hq, Dh), w["q_norm"], cfg["rms_norm_eps"])
    k = rms(k.reshape(T, Hk, Dh), w["k_norm"], cfg["rms_norm_eps"])
    v = v.reshape(T, Hk, Dh)
    seen = pos[None, :] <= pos[:, None]
    if sliding:
        q, k = rope_half(q, pos, float(cfg["rope_theta"])), rope_half(k, pos, float(cfg["rope_theta"]))
        seen = seen & (pos[None, :] > pos[:, None] - cfg["sliding_window"])  # the query's own position counts
    k, v = jnp.repeat(k, Hq // Hk, axis=1), jnp.repeat(v, Hq // Hk, axis=1)  # KV head j: query heads 8j..8j+7
    sc = jnp.einsum("thd,shd->hts", q, k) * Dh ** -0.5
    p = jax.nn.softmax(jnp.where(seen[None], sc.astype(jnp.float32), -jnp.inf), axis=-1).astype(x.dtype)
    o = jnp.einsum("hts,shd->thd", p, v).reshape(T, Hq * Dh)
    return (o * jax.nn.sigmoid(g)) @ w["wo"]


def expert_keys(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The expert layer's numbers under the keys ``perfbench/arch/glm5next.py`` reads."""
    return {"swiglu_limit": math.inf, "num_experts_per_tok": cfg["num_experts_per_tok"],
            "routed_scaling_factor": float(cfg["route_scale"]), "experts_first": cfg["experts_first"],
            "n_routed_experts_held": cfg["num_experts_held"]}


swiglu, moe = _shared.swiglu, _shared.moe


def layer_functions(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """One layer's pieces, each compiled once a sequence length: the two
    mixers and the dense MLP inside their norms, and the two norms
    around the experts (whose loop follows the routing, outside any
    compiled program)."""
    import jax

    eps = cfg["rms_norm_eps"]
    return {
        True: jax.jit(lambda x, w: sublayer(x, w, "attn", cfg, lambda u: attention(u, w, cfg, True))),
        False: jax.jit(lambda x, w: sublayer(x, w, "attn", cfg, lambda u: attention(u, w, cfg, False))),
        "dense": jax.jit(lambda x, w: sublayer(
            x, w, "mlp", cfg, lambda u: swiglu(u, w["w_gate_up"], w["w_down"], math.inf))),
        "read": jax.jit(lambda x, w: rms(x, w["n_mlp_in"], eps)),
        "write": jax.jit(lambda x, y, w: x + rms(y, w["n_mlp_out"], eps)),
        "add_expert": jax.jit(lambda y, x, pad, gate, wg, wd: _shared._add_expert(y, x, pad, gate, wg, wd, math.inf),
                              donate_argnums=(0,)),
    }


_EXPERT_LEAVES = ("we_gate_up", "we_down")


def forward(tokens_list: Sequence[Sequence[int]], cfg: Dict[str, Any], embed, layer_weights, expert_weights,
            final, positions: int, device=None, precision: str = "float32") -> List[np.ndarray]:
    """Logits [T, vocab] per sequence, computed at the last ``positions``
    positions (the rest stays zero: the head is the widest matrix and
    only those rows are compared). Each layer's weights are fetched once
    (``layer_weights(l)``: a dict; ``expert_weights(l)``: the held
    experts' two stacked leaves), applied to all sequences, then
    dropped. ``final`` is (norm weight, head). ``precision="bfloat16"``
    is the control one precision down: nothing in float32, the residual
    row, the norms and the router included."""
    import jax
    import jax.numpy as jnp

    ctx = jax.default_device(device) if device is not None else contextlib.nullcontext()
    dt = jnp.float32 if precision == "float32" else jnp.bfloat16
    kinds, ek = layer_kinds(cfg), expert_keys(cfg)
    t0 = time.time()
    with ctx, jax.default_matmul_precision("highest"):
        fns = layer_functions(cfg)
        cast = lambda a: jnp.asarray(a).astype(dt)  # noqa: E731
        emb = cast(embed)
        mup = math.sqrt(cfg["hidden_size"]) if cfg["mup_enabled"] else 1.0
        # attention is causal, so zeros after a sequence change nothing before
        # them: lengths are rounded up to whole 128s and sequences of one
        # rounded length share their compiled pieces
        padded = [list(t) + [0] * (-len(t) % 128) for t in tokens_list]
        xs = [(emb[np.asarray(t)] * mup).astype(dt) for t in padded]
        for l, (sliding, mlp) in enumerate(kinds):
            w = {k: cast(v) for k, v in layer_weights(l).items()}
            for i, x in enumerate(xs):
                x = fns[sliding](x, w)
                if l == len(kinds) - 1:
                    # the last layer's MLP sublayer mixes no positions: the compared ones only
                    x = x[-(positions + len(padded[i]) - len(tokens_list[i])):]
                xs[i] = x
            if mlp == "dense":
                xs = [fns["dense"](x, w) for x in xs]
            else:
                # the experts see the rows of every sequence at once (a token's MLP reads
                # no other token), so each expert's matrices are fetched once a layer
                us = [fns["read"](x, w) for x in xs]
                held = expert_weights(l)
                y = moe(jnp.concatenate(us), w, ek, lambda e: tuple(cast(a[e]) for a in held), fns["add_expert"])
                del held
                ends = np.cumsum([u.shape[0] for u in us])
                xs = [fns["write"](x, y[end - x.shape[0]:end], w) for x, end in zip(xs, ends)]
            xs = [x.astype(dt) for x in xs]
            jax.block_until_ready(xs)
            del w
            print(f"afmoe reference ({precision}): layer {l} ({'sliding' if sliding else 'full'}, {mlp}) of "
                  f"{len(tokens_list)} sequences done {time.time() - t0:.1f} s in", flush=True)
        norm_w, head_w = (cast(a) for a in final)
        out = []
        for x, tokens, pad in zip(xs, tokens_list, padded):
            T, first = len(tokens), len(pad) - x.shape[0]  # x holds positions first.. of the padded sequence
            x = x[: T - first]
            logits = np.zeros((T, head_w.shape[1]), np.float32)
            h = rms(x, norm_w, cfg["rms_norm_eps"])
            logits[first:] = np.asarray((h.astype(dt) @ head_w).astype(jnp.float32))
            out.append(logits)
        return out


def reference_logits(eng, cfg: Dict[str, Any], sequences: Sequence[Sequence[int]], tp: int = 1,
                     device=None, precision: str = "float32") -> List[np.ndarray]:
    """``forward`` over the engine's own parameter tree; the engine's
    served walks (deferred) run on the chip meanwhile."""
    del tp  # one device serves this stage
    print(f"afmoe reference ({precision}): starts; the launcher's greedy requests are done", flush=True)
    params, host = eng.params, _shared._host
    layer_weights = lambda l: host({k: v for k, v in params["layers"][l].items() if k not in _EXPERT_LEAVES})  # noqa: E731
    expert_weights = lambda l: host(tuple(params["layers"][l][k] for k in _EXPERT_LEAVES))  # noqa: E731
    served = threading.Thread(target=_PENDING.pop(), name="perfbench-served-walks") if _PENDING else None
    if served is not None:
        served.start()
    try:
        return forward(
            sequences, cfg, host(params["embed"]), layer_weights, expert_weights,
            (host(params["final_norm"]), host(params["head"])),
            positions=int(cfg["reference"]["decode_tokens"]) + 1, device=device, precision=precision,
        )
    finally:
        if served is not None:
            served.join()


# --------------------------------------------------------------------------- #
# Bytes and operations of a decode step and of the grouped matmul


def _sizes(cfg: Dict[str, Any]) -> Dict[str, float]:
    D, Dh = cfg["hidden_size"], cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * Dh, cfg["num_key_value_heads"] * Dh
    kinds = layer_kinds(cfg)
    return {
        "D": D, "q": q, "kv": kv, "n": len(kinds),
        "attn": D * (2 * q + 2 * kv) + q * D,  # bfloat16 elements
        "norms_f32": 4 * D + 2 * Dh,
        "dense": 3 * D * cfg["intermediate_size"],
        "shared": 3 * D * cfg["moe_intermediate_size"],
        "router_f32": D * cfg["num_experts"] + cfg["num_experts"],
        "expert": 3 * D * cfg["moe_intermediate_size"],
        "n_window": sum(1 for s, _ in kinds if s), "n_full": sum(1 for s, _ in kinds if not s),
        "n_dense": sum(1 for _, f in kinds if f == "dense"), "n_sparse": sum(1 for _, f in kinds if f == "sparse"),
    }


def expert_bytes(cfg: Dict[str, Any]) -> int:
    """bfloat16 bytes of ONE routed expert's three matrices (12,582,912 at the published widths)."""
    return int(2 * _sizes(cfg)["expert"])


def fixed_weight_bytes(cfg: Dict[str, Any]) -> float:
    """Weights a decode step reads whatever it routes: everything outside
    the routed experts, and the head over the whole vocabulary."""
    s = _sizes(cfg)
    bf16 = s["n"] * s["attn"] + s["n_dense"] * s["dense"] + s["n_sparse"] * s["shared"]
    f32 = s["n"] * s["norms_f32"] + s["D"] + s["n_sparse"] * s["router_f32"]
    return 2.0 * (bf16 + s["D"] * cfg["vocab_size"]) + 4.0 * f32


def expected_experts_hit(cfg: Dict[str, Any], rows: float) -> float:
    """Held experts a step of ``rows`` tokens reaches, summed over the
    expert layers, under a uniform router: ``held (1 - (1 - k/E)^rows)``."""
    p = 1.0 - (1.0 - cfg["num_experts_per_tok"] / cfg["num_experts"]) ** max(rows, 0.0)
    return _sizes(cfg)["n_sparse"] * cfg["num_experts_held"] * p


def decode_step_bytes(cfg: Dict[str, Any], rows: float, mean_context: float,
                      experts_hit: Optional[float] = None, window_tokens: Optional[float] = None,
                      full_tokens: Optional[float] = None) -> float:
    """HBM bytes one decode step of ``rows`` sequences must move: the
    fixed weights once; the matrices of the experts HIT (summed over the
    expert layers: measured where the spans give it, else the uniform
    router's expectation); the ring rows the window layers read and the
    cached tokens the full layer read (K and V of 4 heads of 128 each:
    measured where the spans give them, else from the mean context); per
    row the new K/V rows and an embedding row."""
    s = _sizes(cfg)
    hit = expected_experts_hit(cfg, rows) if experts_hit is None else experts_hit
    if window_tokens is None:
        window_tokens = rows * s["n_window"] * min(mean_context + 1, cfg["sliding_window"])
    if full_tokens is None:
        full_tokens = rows * s["n_full"] * (mean_context + 1)
    token_bytes = 2 * 2 * s["kv"]  # K and V, bfloat16
    per_row = s["n"] * token_bytes + 2 * s["D"]
    return (fixed_weight_bytes(cfg) + hit * expert_bytes(cfg) + (window_tokens + full_tokens) * token_bytes
            + rows * per_row)


def decode_step_flops(cfg: Dict[str, Any], rows: float, mean_context: float) -> float:
    """Multiply-adds x 2 a step: every fixed matrix once a row, a row's 8
    experts, scores and values of 32 heads over the window (four layers)
    and over the whole context (one)."""
    s = _sizes(cfg)
    held = cfg["num_experts_per_tok"] * cfg["num_experts_held"] / cfg["num_experts"]
    fixed = (s["n"] * s["attn"] + s["n_dense"] * s["dense"]
             + s["n_sparse"] * (s["shared"] + s["router_f32"] + held * s["expert"]) + s["D"] * cfg["vocab_size"])
    keys = s["n_window"] * min(mean_context + 1, cfg["sliding_window"]) + s["n_full"] * (mean_context + 1)
    return 2.0 * rows * (fixed + 2 * s["q"] * keys)


def decode_step_floor_s(cfg: Dict[str, Any], peaks: Dict[str, float], rows: float, mean_context: float,
                        experts_hit: Optional[float] = None, window_tokens: Optional[float] = None,
                        full_tokens: Optional[float] = None) -> float:
    t_bytes = decode_step_bytes(cfg, rows, mean_context, experts_hit, window_tokens, full_tokens) / peaks["hbm_bytes_per_s"]
    t_flops = decode_step_flops(cfg, rows, mean_context) / peaks["bf16_flops_per_s"]
    return max(t_bytes, t_flops)


# --------------------------------------------------------------------------- #
# Readers of this architecture's own spans


def decode_roofline_share(ctx, params) -> Optional[float]:
    """``decode_step_floor_s`` with the experts HIT, the ring rows and
    the full-layer tokens READ a step that the decode spans report, over
    the measured device time of a step, percent."""
    from perfbench import readers

    step_ms = ctx["read"](params["time_metric"])
    rows = readers.span_mean(ctx, {"kind": "decode", "field": "rows"})
    hit = readers.span_mean(ctx, {"kind": "decode", "field": "moe_experts_hit"})
    window = readers.span_mean(ctx, {"kind": "decode", "field": "window_tokens_read"})
    full = readers.span_mean(ctx, {"kind": "decode", "field": "full_tokens_read"})
    if not step_ms or not rows or hit is None or window is None or full is None:
        return None
    context = full / max(1, _sizes(ctx["config"])["n_full"]) / rows
    floor_s = decode_step_floor_s(ctx["config"], ctx["peaks"], rows, context, hit, window, full)
    return 100.0 * floor_s / (step_ms / 1000.0)


def grouped_matmul_roofline_share(ctx, params) -> Optional[float]:
    """Bytes of the experts HIT in the traced interval over the HBM peak,
    over the grouped-matmul kernels' self time there, percent. Bytes: the
    programs the trace counted (decode blocks of ``decode_block`` steps,
    extend chunks) times the experts a step / a chunk hit in the window's
    spans, times an expert's three matrices."""
    from perfbench import readers, trace_reduce

    tr = ctx["trace"]
    if not tr or not tr.get("devices"):
        return None
    self_s = trace_reduce.matching_s(tr["ops_self_s"], params["match"])
    hit_step = readers.span_mean(ctx, {"kind": "decode", "field": "moe_experts_hit"})
    if not self_s or hit_step is None:
        return None
    hit_chunk = readers.span_mean(ctx, {"kind": "prefill_chunk", "field": "moe_experts_hit"}) or 0.0
    block = float(ctx["config"]["engine"].get("decode_block", 1) or 1)
    hits = (_shared._programs_traced(tr, r"^jit_decode") * block * hit_step
            + _shared._programs_traced(tr, r"^jit_extend") * hit_chunk)
    return 100.0 * hits * expert_bytes(ctx["config"]) / ctx["peaks"]["hbm_bytes_per_s"] / self_s


def window_read_share(ctx, params) -> Optional[float]:
    """What the window layers' reads are of all attention reads of the
    decode steps in the window: ``window_tokens_read / (window_tokens_read
    + full_tokens_read)``, percent. Spans without the fields (the parent)
    give nothing to read."""
    del params
    pairs = [(float(s["window_tokens_read"]), float(s["full_tokens_read"])) for s in ctx["spans"]
             if s.get("kind") == "decode" and "window_tokens_read" in s and "full_tokens_read" in s]
    total = sum(a + b for a, b in pairs)
    return 100.0 * sum(a for a, _ in pairs) / total if total > 0 else None
