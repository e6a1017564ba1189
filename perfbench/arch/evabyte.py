"""The adapter of EvaByte (``evabyte``, 6.5B, byte-level) as one stage
of a four-stage pipeline (contract: ``perfbench/arch/__init__.py``).

**Registration.** ``register`` writes the configuration file's published
widths and the layers served into the program's model registry under the
configuration's name, as a configuration of the ``evabyte`` family.

**The plain reference**: float32 ``jax.numpy`` written from the layer
equations of ISSUE 57 (EVA: Zheng et al., ICLR 2023, arXiv:2302.04542;
what the published ``config.json`` does not carry is listed under
``assumed`` in the configuration file), importing nothing of the
program: no kernel, no cache, no buffer, no pages, no batching. It reads
the engine's OWN bfloat16 weights, layer by layer, and widens them to
float32. Per layer, with ``x [T, D]`` the float32 residual rows of one
sequence (``fp32_skip_add``) and ``N(u) = u / sqrt(mean(u^2) + 1e-5) *
(1 + g)`` (``norm_add_unit_offset``):

- ``a = N_1(x)``; ``q, k, v = W_q a, W_k a, W_v a`` (the engine holds the
  three as one matrix ``wqkv``; the reference splits it), 32 heads of
  128, no bias; q and k rotated over all 128 dims in pairs ``(i, i +
  64)``, theta 1e5, position ``t``. ``s = 128^-0.5``, ``W = 2048``, ``C =
  16``. Per head ``h`` with learned ``mu_h, phi_h in R^128``:
- chunk ``c`` = tokens ``[16 c, 16 c + 16)``: ``alpha_m = softmax_{m in
  c}(mu_h . k_m)``, ``kbar_c = sum_m alpha_m k_m``; ``beta_m =
  softmax_{m in c}(s (phi_h . k_m - |k_m|^2 / 2))``, ``vbar_c = sum_m
  beta_m v_m`` (the self-normalised random-feature estimate of EVA with
  the learned ``phi`` in the place of the sampled ``omega``); both
  softmaxes in float32 (``mixedp_attn``).
- query ``t``, window ``w = t // W``: exact set ``E_t = {m : w W <= m <=
  t}``, summary set ``S_t = {c : c < 128 w}`` (every chunk of every
  CLOSED window, none of its own). ONE softmax over both: ``o_t =
  (sum_{E_t} e^{s q.k_m} v_m + sum_{S_t} e^{s q.kbar_c} vbar_c) /
  (sum_{E_t} e^{s q.k_m} + sum_{S_t} e^{s q.kbar_c})``.
- ``x <- x + W_o o``; ``x <- x + W_down(silu(W_gate b) * W_up b)``, ``b =
  N_2(x)``, width 11008 (``w_gate_up`` holds ``[gate | up]``).
- ``logits = W_head N_f(x)`` in float32 (``fp32_logits``), ``W_head
  [4096, 8 x 320]``: columns ``[320 j, 320 j + 320)`` predict byte ``t +
  1 + j``. Embedding ``[320, 4096]``, untied.

Two identities tie this to plain attention (``tests/test_evabyte.py``):
with ``C = 1`` both chunk softmaxes are over one element, so ``kbar =
k``, ``vbar = v`` and the layer IS full causal attention; with ``T <= W``
no summary is visible and it IS causal attention over the window.

DEPARTURES, none of the mathematics: the masks ``E_t`` / ``S_t`` are
evaluated a WINDOW of queries at a time (the queries of window ``w``
against that window's keys and every summary, under the two masks): keys
of other windows are masked for every query of the block, so they are
not loaded; at 12 k tokens the whole ``[32, T, T]`` score tensor would be
20 GB. The last layer's MLP and the head run on the compared positions
alone (they mix no positions).

**What is compared.** ``reference.compare`` holds the next byte's 320
logits at each compared prompt's last position, and the served tokens'
margin, to ``TOLERANCE``; the other seven output heads' 2,240 logits are
held to the same limit HERE (``reference_logits`` raises where the served
walks' row of all 2,560 is further from the reference's than
``TOLERANCE``: the run then ends ``correct: false`` with the readings in
its log), because the harness's margin takes the maximum of a reference
row and a row of eight heads has eight maxima.

``TOLERANCE``: the two readings it sits between are written beside it
below (PERF.md section 6, PR 57).

**Bytes and operations** of a decode step (``decode_step_bytes``,
``decode_step_flops``) and of the read (``read_bytes_and_flops``) are
counted here, so that no PR which claims a gain can change the count.
``fault`` (``forward``) plants one of four WRONG forms of the layer in
the reference, for the tests that show the limit separates them.
"""
from __future__ import annotations

import contextlib
import threading
import time
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from perfbench.arch import glm5next as _shared  # Deferred, the host read-back and the span helpers

# The two readings (PERF.md section 6, PR 57; my chip runs, one TPU v5 lite), prompts of 64,
# 1,040, 2,044 and 4,090 tokens, published widths, eight layers, all eight output heads:
# - the engine's SERVED walks on the chip against this float32 reference, all 2,560 logits:
#   0.0050 (64 tokens extended whole, then the head), 0.0011 (1,039 extended in three chunks
#   and one decode step through the compiled read, which completes a chunk), 0.0010 (2,043 and
#   a step at the end of the first window), 0.0007 (4,089 and a step 2,042 rows into the third
#   window with 256 summaries behind it); the next byte's 320 alone 0.0053 / 0.0010 / 0.0014 /
#   0.0009; the served tokens' margin 0.0 over 40 tokens (every greedy token through buffer and
#   pages, two windows closing on the way, is the reference's own argmax). Prompts and weights
#   are fixed, so the numbers repeat to the digit from run to run (thirteen runs of the final
#   tree); the chunk walk's first form (all heads' scores at once) read 0.0048 where this one
#   reads 0.0053.
# - the control one precision down (``precision="bfloat16"``: nothing in float32, the residual
#   row, the norms and both softmaxes included; ``python3 -m perfbench.arch.evabyte`` on the
#   chip machine's host) against the same reference on the same weights: all heads 0.0175 /
#   0.0154 / 0.0165 / 0.0129, the next byte's 0.0187 / 0.0158 / 0.0155 / 0.0183: NOT correct by
#   prefill_rel_err, that limit alone (its own tokens' margin is its own business), on every prompt.
# 0.008 lies 1.5 above the served walks' largest reading (0.0053) and 1.6 below the control's
# smallest (0.0129); their geometric mean is 0.0083. The short prompt reads five times the
# long ones: 64 keys average a head's bfloat16 values less than 2,000 do.
TOLERANCE = 0.008

FAULTS = ("no_ksq", "no_mu", "no_phi", "two_softmax", "int8_summaries")
_PENDING: List[Any] = []  # the deferred walks of the last engine_prefill_logits call
_SERVED_ALL: List[np.ndarray] = []  # their rows over all eight output heads
Deferred = _shared.Deferred


# --------------------------------------------------------------------------- #
# The engine's side


def model_config(cfg: dict):
    from generativeaiexamples_tpu.models.evabyte import EvaByteConfig

    return EvaByteConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"], intermediate_size=cfg["intermediate_size"],
        num_hidden_layers=cfg["num_hidden_layers"], layers_served=tuple(cfg["layers_served"]),
        num_heads=cfg["num_attention_heads"], head_dim=cfg["hidden_size"] // cfg["num_attention_heads"],
        chunk_size=cfg["chunk_size"], window_size=cfg["window_size"], num_pred_heads=cfg["num_pred_heads"],
        rope_theta=float(cfg["rope_theta"]), norm_eps=float(cfg["rms_norm_eps"]),
        max_seq_len=cfg["engine"]["max_seq_len"],
    )


def register(cfg: dict) -> None:
    from generativeaiexamples_tpu.models import registry

    registry.register_preset("evabyte", cfg["name"], model_config(cfg))


def engine_prefill_logits(eng, prompts, on_tpu: bool):
    """Last-prompt-position logits from the walks the engine SERVES with
    (its family's ``extend_paged``, ``decode_paged`` and ``head``, with
    the kernel paths it resolved), on the engine's weights, in the
    engine's shapes for one row, over a scratch cache of ONE slot that
    goes from prompt to prompt as the last one left it, so every
    admission meets a former tenant's buffer and pages. The FIRST prompt
    is extended whole and read by the head; every other one is extended
    in chunks up to its last token, which then takes ONE decode step: the
    read of the buffer and, past one window, of the summary pages. A row
    handed to ``reference.compare`` is the next byte's 320 logits; the
    rows over all eight heads are kept for ``reference_logits``.

    The rows are ``Deferred``: the walks run when the first is read (the
    launcher's greedy requests enter the queue first)."""
    del on_tpu
    done: Dict[str, Any] = {}

    def compute():
        if "rows" not in done:
            _SERVED_ALL[:] = _served_logits(eng, [list(p) for p in prompts])
            done["rows"] = [row[: eng.model_config.vocab_size] for row in _SERVED_ALL]
        return done["rows"]

    _PENDING[:] = [compute]
    return [Deferred(compute, i) for i in range(len(prompts))]


def _served_logits(eng, prompts) -> List[np.ndarray]:
    import jax
    import jax.numpy as jnp

    from generativeaiexamples_tpu.models import evabyte as model

    fam, cfg, params = eng._family, eng.model_config, eng.params
    C, page = eng.engine_config.prefill_chunk, eng.engine_config.page_size
    pmax = -(-max(len(p) for p in prompts) // cfg.window_size) * cfg.window_size // page
    tables = jnp.asarray(1 + np.arange(pmax, dtype=np.int32)[None, :])  # page 0 is the scratch page
    caches = fam.init_paged_cache(cfg, 1 + pmax, page, 1, eng._cache["win"][0]["k"].dtype)
    slot = jnp.zeros((1,), jnp.int32)
    one = lambda n: jnp.asarray([n], jnp.int32)  # noqa: E731
    paths = dict(eng._family_kernels)

    def extend_and_head(params, caches, tok, off, n):
        hidden, caches = fam.extend_paged(params, cfg, caches, tok, off, n, slot, tables, pmax * page, page, **paths)
        return model.head(params, cfg, hidden, all_heads=True), caches

    # the scratch cache is DONATED from walk to walk, as the engine donates its own: a second copy of a slot's
    # eight window buffers (0.27 GB) beside a chip that is 90 % full is what the first chip runs peaked on
    extend = jax.jit(extend_and_head, donate_argnums=(1,))
    decode = jax.jit(lambda params, caches, tok, pos: fam.decode_paged(
        params, cfg, caches, tok, pos, jnp.ones((1,), bool), tables, pmax * page, page, all_heads=True, **paths),
        donate_argnums=(1,))

    def chunk(tokens):
        row = np.zeros((1, C), np.int32)
        row[0, : len(tokens)] = tokens
        return jnp.asarray(row)

    out = []
    for i, p in enumerate(prompts):
        body = p if i == 0 else p[:-1]
        for k in range(0, len(body), C):
            # genai-lint: disable=shape-cardinality -- offsets and lengths as [1] values
            logits, caches = extend(params, caches, chunk(body[k:k + C]), one(k), one(min(C, len(body) - k)))
        if i:
            logits, caches = decode(params, caches, one(p[-1]), one(len(p) - 1))  # genai-lint: disable=shape-cardinality -- a position as a [1] value
        out.append(np.asarray(logits, np.float32)[0])
    return out


# --------------------------------------------------------------------------- #
# The plain float32 reference (imports nothing of the program)


def rms(u, g, eps: float):
    import jax.numpy as jnp

    return u / jnp.sqrt(jnp.mean(u * u, axis=-1, keepdims=True) + eps) * (1.0 + g)


def rope_half(x, positions, theta: float):
    """RoPE over the whole last axis in pairs ``(i, i + Dh / 2)``; x [T, H, Dh], positions [T]."""
    import jax.numpy as jnp

    half = x.shape[-1] // 2
    inv_freq = (theta ** (-np.arange(half, dtype=np.float64) / half)).astype(np.float32)
    ang = jnp.asarray(positions, jnp.float32)[:, None, None] * inv_freq[None, None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return (x * jnp.cos(ang) + rotated * jnp.sin(ang)).astype(x.dtype)


def chunk_summaries(k, v, mu, phi, chunk: int, fault: Optional[str] = None):
    """k, v [n C, H, Dh] (whole chunks) -> (kbar, vbar) [n, H, Dh]."""
    import jax
    import jax.numpy as jnp

    n, (_, H, Dh) = k.shape[0] // chunk, k.shape
    kc, vc = k.reshape(n, chunk, H, Dh), v.reshape(n, chunk, H, Dh)
    s = Dh ** -0.5
    pool_k = jnp.einsum("nchd,hd->nch", kc, mu).astype(jnp.float32)
    feat = jnp.einsum("nchd,hd->nch", kc, phi) - 0.5 * jnp.sum(kc * kc, axis=-1)
    if fault == "no_mu":
        pool_k = jnp.zeros_like(pool_k)
    if fault == "no_phi":
        feat = -0.5 * jnp.sum(kc * kc, axis=-1)
    if fault == "no_ksq":
        feat = jnp.einsum("nchd,hd->nch", kc, phi)
    alpha = jax.nn.softmax(pool_k, axis=1).astype(k.dtype)
    beta = jax.nn.softmax((s * feat).astype(jnp.float32), axis=1).astype(k.dtype)
    kbar, vbar = jnp.einsum("nch,nchd->nhd", alpha, kc), jnp.einsum("nch,nchd->nhd", beta, vc)
    if fault == "int8_summaries":  # a pool of int8 rows with one scale a summary row (all heads)
        def int8(x):
            scale = jnp.max(jnp.abs(x), axis=(-2, -1), keepdims=True) / 127.0
            return jnp.round(x / scale) * scale
        kbar, vbar = int8(kbar), int8(vbar)
    return kbar, vbar


def attention(a, w: Dict[str, Any], cfg: Dict[str, Any], fault: Optional[str] = None):
    """a [T, D] normed -> [T, D]: the exact window and the summaries of the closed windows under ONE softmax."""
    import jax
    import jax.numpy as jnp

    H = cfg["num_attention_heads"]
    Dh = cfg["hidden_size"] // H
    W, C = cfg["window_size"], cfg["chunk_size"]
    T = a.shape[0]
    pos = np.arange(T)
    q, k, v = (y.reshape(T, H, Dh) for y in jnp.split(a @ w["wqkv"], 3, axis=1))
    q, k = rope_half(q, pos, float(cfg["rope_theta"])), rope_half(k, pos, float(cfg["rope_theta"]))
    s = Dh ** -0.5
    n_sum = (T // W) * (W // C)  # chunks of closed windows: the only ones any query sees
    if n_sum:
        kbar, vbar = chunk_summaries(k[: n_sum * C], v[: n_sum * C], w["mu"], w["phi"], C, fault)
    out = []
    for start in range(0, T, W):  # the queries of one window at a time
        t = pos[start:start + W]
        in_window = t[None, :] <= t[:, None]  # E_t, over the window's own keys
        sc = jnp.einsum("thd,shd->hts", q[start:start + W], k[start:start + W]) * s
        sc = jnp.where(in_window[None], sc.astype(jnp.float32), -jnp.inf)
        vals = v[start:start + W]
        if n_sum and start:
            closed = np.arange(n_sum)[None, :] < (W // C) * (t // W)[:, None]  # S_t
            sc_sum = jnp.einsum("thd,nhd->htn", q[start:start + W], kbar) * s
            sc_sum = jnp.where(closed[None], sc_sum.astype(jnp.float32), -jnp.inf)
            if fault == "two_softmax":  # two reads, each normalised by itself, then averaged
                o = 0.5 * (jnp.einsum("hts,shd->thd", jax.nn.softmax(sc, axis=-1).astype(a.dtype), vals)
                           + jnp.einsum("htn,nhd->thd", jax.nn.softmax(sc_sum, axis=-1).astype(a.dtype), vbar))
                out.append(o)
                continue
            sc, vals = jnp.concatenate([sc, sc_sum], axis=-1), jnp.concatenate([vals, vbar], axis=0)
        out.append(jnp.einsum("hts,shd->thd", jax.nn.softmax(sc, axis=-1).astype(a.dtype), vals))
    return jnp.concatenate(out, axis=0).reshape(T, H * Dh) @ w["wo"]


def mlp(b, w: Dict[str, Any]):
    import jax

    gu = b @ w["w_gate_up"]
    F = gu.shape[-1] // 2
    return (jax.nn.silu(gu[:, :F]) * gu[:, F:]) @ w["w_down"]


def forward(tokens_list: Sequence[Sequence[int]], cfg: Dict[str, Any], embed, layer_weights, final,
            positions: int, device=None, precision: str = "float32", fault: Optional[str] = None) -> List[np.ndarray]:
    """Logits [T, 8 x vocab] per sequence, computed at the last
    ``positions`` positions (the rest stays zero: only those rows are
    compared). Each layer's weights are fetched once (``layer_weights(l)``:
    a dict), applied to all sequences, then dropped. ``final`` is (the
    final norm's ``g``, head). ``precision="bfloat16"`` is the control one
    precision down: nothing in float32, the residual row, the norms and
    the softmaxes' inputs included. ``fault``: one of ``FAULTS``."""
    import jax
    import jax.numpy as jnp

    ctx = jax.default_device(device) if device is not None else contextlib.nullcontext()
    dt = jnp.float32 if precision == "float32" else jnp.bfloat16
    eps, n_layers = cfg["rms_norm_eps"], len(cfg["layers_served"])
    t0 = time.time()
    with ctx, jax.default_matmul_precision("highest"):
        attn = jax.jit(lambda x, w: (x + attention(rms(x, w["n1"], eps).astype(dt), w, cfg, fault)).astype(dt))
        ffn = jax.jit(lambda x, w: (x + mlp(rms(x, w["n2"], eps).astype(dt), w)).astype(dt))
        cast = lambda a: jnp.asarray(a).astype(dt)  # noqa: E731
        emb = cast(embed)
        xs = [emb[np.asarray(t)] for t in tokens_list]
        for l in range(n_layers):
            w = {k: cast(v) for k, v in layer_weights(l).items()}
            for i, x in enumerate(xs):
                x = attn(x, w)
                if l == n_layers - 1:
                    x = x[-positions:]  # the last MLP mixes no positions: the compared ones only
                xs[i] = ffn(x, w)
            jax.block_until_ready(xs)
            del w
            print(f"evabyte reference ({precision}): layer {l} of {len(tokens_list)} sequences done "
                  f"{time.time() - t0:.1f} s in", flush=True)
        norm_g, head_w = (cast(a) for a in final)
        out = []
        for x, tokens in zip(xs, tokens_list):
            logits = np.zeros((len(tokens), head_w.shape[1]), np.float32)
            logits[len(tokens) - x.shape[0]:] = np.asarray(
                (rms(x, norm_g, eps).astype(dt) @ head_w).astype(jnp.float32))
            out.append(logits)
        return out


def reference_logits(eng, cfg: Dict[str, Any], sequences: Sequence[Sequence[int]], tp: int = 1,
                     device=None, precision: str = "float32") -> List[np.ndarray]:
    """``forward`` over the engine's own parameter tree; the engine's
    served walks (deferred) run on the chip meanwhile. Returns the next
    byte's logits ``[T, vocab]`` a sequence; holds the served walks' rows
    over ALL output heads to the full reference first (raises past
    ``TOLERANCE``)."""
    del tp  # one device serves this stage
    print(f"evabyte reference ({precision}): starts; the launcher's greedy requests are done", flush=True)
    params, host = eng.params, _shared._host
    served = threading.Thread(target=_PENDING.pop(), name="perfbench-served-walks") if _PENDING else None
    if served is not None:
        served.start()
    try:
        full = forward(
            sequences, cfg, host(params["embed"]), lambda l: host(params["layers"][l]),
            (host(params["final_norm"]), host(params["head"])),
            positions=int(cfg["reference"]["decode_tokens"]) + 1, device=device, precision=precision,
        )
    finally:
        if served is not None:
            served.join()
    if served is not None:
        errs = all_heads_rel_err(full, cfg["reference"]["prompt_tokens"], _SERVED_ALL)
        print(f"evabyte reference: served rows over all {cfg['num_pred_heads']} output heads against the reference: "
              f"rel err {errs} (limit {TOLERANCE})", flush=True)
        if not errs or max(errs) > TOLERANCE:
            raise RuntimeError(f"the served walks' logits over all output heads are not the reference's: {errs}")
    return [row[:, : cfg["vocab_size"]] for row in full]


def all_heads_rel_err(full: Sequence[np.ndarray], prompt_tokens: Sequence[int],
                      served: Sequence[np.ndarray]) -> List[float]:
    """max|served - reference| / max|reference| over ALL output heads at each compared prompt's last position."""
    errs = []
    for ref, T, row in zip(full, prompt_tokens, served):
        last = ref[T - 1]
        scale = max(float(np.max(np.abs(last))), 1e-6)
        errs.append(float(np.max(np.abs(np.asarray(row, np.float32) - last)) / scale)
                    if np.all(np.isfinite(row)) else float("inf"))
    return errs


# --------------------------------------------------------------------------- #
# Bytes and operations of a decode step and of the read


def _sizes(cfg: Dict[str, Any]) -> Dict[str, float]:
    D, F, V = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    return {
        "D": D, "n": len(cfg["layers_served"]), "W": cfg["window_size"], "C": cfg["chunk_size"],
        "layer": 4 * D * D + 3 * D * F,  # bfloat16 elements
        "layer_f32": 2 * D + 2 * D,  # two norms, mu and phi (32 x 128 each)
        "head": D * V * cfg["num_pred_heads"], "embed": V * D,
        "row_bytes": 2 * 2 * D,  # a K and a V row over every head, bfloat16: exact key or summary alike
    }


def weight_bytes(cfg: Dict[str, Any]) -> float:
    """Weights a decode step reads: every layer once, and the next byte's output head (320 of 2,560 columns)."""
    s = _sizes(cfg)
    return 2.0 * (s["n"] * s["layer"] + s["head"] / cfg["num_pred_heads"]) + 4.0 * (s["n"] * s["layer_f32"] + s["D"])


def rows_read(cfg: Dict[str, Any], context: float) -> tuple:
    """(exact keys, summaries) ONE layer's read of a row at ``context`` cached tokens covers."""
    W, C = cfg["window_size"], cfg["chunk_size"]
    return context % W + 1, (context // W) * (W // C)


def read_bytes_and_flops(cfg: Dict[str, Any], window_rows: float, summary_rows: float) -> tuple:
    """HBM bytes and multiply-adds x 2 of reading ``window_rows`` exact
    keys and ``summary_rows`` summaries (each summed over rows and
    layers): a K and a V row of 32 x 128 bfloat16 each; scores and values
    of 32 heads of 128."""
    s = _sizes(cfg)
    n = window_rows + summary_rows
    return n * s["row_bytes"], 2.0 * 2.0 * n * s["D"]


def _step_rows(cfg: Dict[str, Any], rows: float, mean_context: float,
               window_rows: Optional[float], summary_rows: Optional[float]) -> tuple:
    """(exact keys, summaries) a step reads over its rows and layers: as measured, else from the mean context."""
    if window_rows is not None and summary_rows is not None:
        return window_rows, summary_rows
    exact, summaries = rows_read(cfg, mean_context)
    n = rows * len(cfg["layers_served"])
    return n * exact, n * summaries


def decode_step_bytes(cfg: Dict[str, Any], rows: float, mean_context: float,
                      window_rows: Optional[float] = None, summary_rows: Optional[float] = None) -> float:
    """HBM bytes one decode step of ``rows`` sequences must move: the
    weights once; the exact keys and the summaries its reads cover
    (measured where the spans give them, else from the mean context); per
    row the new K and V rows and an embedding row."""
    s = _sizes(cfg)
    nbytes, _ = read_bytes_and_flops(cfg, *_step_rows(cfg, rows, mean_context, window_rows, summary_rows))
    return weight_bytes(cfg) + nbytes + rows * (s["n"] * s["row_bytes"] + 2 * s["D"])


def decode_step_flops(cfg: Dict[str, Any], rows: float, mean_context: float,
                      window_rows: Optional[float] = None, summary_rows: Optional[float] = None) -> float:
    s = _sizes(cfg)
    _, flops = read_bytes_and_flops(cfg, *_step_rows(cfg, rows, mean_context, window_rows, summary_rows))
    return 2.0 * rows * (s["n"] * s["layer"] + s["head"] / cfg["num_pred_heads"]) + flops


def decode_step_floor_s(cfg: Dict[str, Any], peaks: Dict[str, float], rows: float, mean_context: float,
                        window_rows: Optional[float] = None, summary_rows: Optional[float] = None) -> float:
    t_bytes = decode_step_bytes(cfg, rows, mean_context, window_rows, summary_rows) / peaks["hbm_bytes_per_s"]
    t_flops = decode_step_flops(cfg, rows, mean_context, window_rows, summary_rows) / peaks["bf16_flops_per_s"]
    return max(t_bytes, t_flops)


# --------------------------------------------------------------------------- #
# Readers of this architecture's own spans. A program without the stats (the parent) gives nothing to read.


def _step_reads(ctx) -> Optional[tuple]:
    """(exact keys, summaries) a decode step read, summed over its rows and layers, as the spans say."""
    from perfbench import readers

    window = readers.span_mean(ctx, {"kind": "decode", "field": "eva_window_tokens_read"})
    summaries = readers.span_mean(ctx, {"kind": "decode", "field": "eva_summaries_read"})
    return None if window is None or summaries is None else (window, summaries)


def row_layer_mean(ctx, params) -> Optional[float]:
    """Mean of a decode span's ``field`` over its rows and the layers: what ONE row's read of ONE layer covered."""
    vals = [float(s[params["field"]]) / (float(s["rows"]) * float(s["eva_layers"])) for s in ctx["spans"]
            if s.get("kind") == "decode" and params["field"] in s and s.get("rows") and s.get("eva_layers")]
    return sum(vals) / len(vals) if vals else None


def summary_rows_share(ctx, params) -> Optional[float]:
    """Summaries over all rows the decode steps of the window read, percent."""
    del params
    r = _shared._span_ratio(ctx, "decode", "eva_summaries_read", "eva_window_tokens_read")
    return None if r is None else 100.0 * r / (1.0 + r)


def read_roofline_share(ctx, params) -> Optional[float]:
    """The least time the chip could take for the exact keys and the
    summaries the decode steps of the traced interval READ (the larger of
    their bytes over the HBM peak and of the read's operations over the
    bf16 peak), over the self time there of the operations that match
    (the read's kernel), percent. Rows: the decode steps the trace
    counted times what a step read, over its rows and layers, in the
    window's spans: the same work whatever implements the read."""
    from perfbench import trace_reduce

    tr, reads = ctx["trace"], _step_reads(ctx)
    if not tr or not tr.get("devices") or reads is None:
        return None
    self_s = trace_reduce.matching_s(tr["ops_self_s"], params["match"])
    if not self_s:
        return None
    steps = _shared._programs_traced(tr, r"^jit_decode") * float(ctx["config"]["engine"].get("decode_block", 1) or 1)
    nbytes, flops = read_bytes_and_flops(ctx["config"], steps * reads[0], steps * reads[1])
    floor_s = max(nbytes / ctx["peaks"]["hbm_bytes_per_s"], flops / ctx["peaks"]["bf16_flops_per_s"])
    return 100.0 * floor_s / self_s


def decode_roofline_share(ctx, params) -> Optional[float]:
    """``decode_step_floor_s`` with the exact keys and the summaries READ
    a step that the decode spans report, over the measured device time of
    a step, percent."""
    from perfbench import readers

    step_ms = ctx["read"](params["time_metric"])
    rows = readers.span_mean(ctx, {"kind": "decode", "field": "rows"})
    reads = _step_reads(ctx)
    if not step_ms or not rows or reads is None:
        return None
    floor_s = decode_step_floor_s(ctx["config"], ctx["peaks"], rows, 0.0, reads[0], reads[1])
    return 100.0 * floor_s / (step_ms / 1000.0)


# --------------------------------------------------------------------------- #
# Once, outside the per-run comparison (``python3 -m perfbench.arch.evabyte``): the control's reading that
# ``TOLERANCE`` sits under. Both sides are this file's reference on the host CPU, so any machine with the memory
# gives it; the weights are the engine's initialiser's, seed 0, drawn where jax draws them.


def control_readings(cfg: Dict[str, Any]) -> Dict[str, List[float]]:
    """max|control - reference| / max|reference| at each compared prompt's last position, over all output heads
    and over the next byte's alone, for the all-bfloat16 control against the float32 reference."""
    import jax
    import jax.numpy as jnp

    from generativeaiexamples_tpu.models import evabyte as model
    from generativeaiexamples_tpu.utils import jax_env
    from perfbench import reference

    jax_env.bootstrap()
    params = model.init_params_fast(model_config(cfg), 0, jnp.bfloat16)
    lengths = cfg["reference"]["prompt_tokens"]
    prompts = reference.seeded_prompts(lengths, cfg["vocab_size"], seed=20240924)
    host, cpu = _shared._host, jax.devices("cpu")[0]
    args = (prompts, cfg, host(params["embed"]), lambda l: host(params["layers"][l]),
            (host(params["final_norm"]), host(params["head"])))
    full = forward(*args, positions=1, device=cpu)
    control = forward(*args, positions=1, device=cpu, precision="bfloat16")
    V = cfg["vocab_size"]
    return {"all_heads": all_heads_rel_err(full, lengths, [c[-1] for c in control]),
            "next_byte": all_heads_rel_err([f[:, :V] for f in full], lengths, [c[-1, :V] for c in control])}


if __name__ == "__main__":
    import json
    import os
    import sys

    path = sys.argv[1] if len(sys.argv) > 1 else os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "configs", "evabyte-6.5b-pp4-bf16.json")
    with open(path, encoding="utf-8") as fh:
        print("evabyte control (bfloat16 throughout) against the float32 reference:",
              json.dumps(control_readings(json.load(fh))), flush=True)
