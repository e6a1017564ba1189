"""The adapter of Solar-Open2-250B (``solar_open2``, 250B-A15B) as one
chip's share of an 8-way expert-parallel deployment (contract:
``perfbench/arch/__init__.py``).

**Registration.** ``register`` writes the configuration file's published
widths, the layers served and the chip's share (40 of 320 experts, an
eighth of the vocabulary) into the program's model registry under the
configuration's name, as a configuration of the ``solaropen2`` family.

**The plain reference**: float32 ``jax.numpy`` written from the layer
equations of ISSUE 44, importing nothing of the program: no kernel, no
cache, no batching, the recurrence token by token, whole-sequence causal
softmax (a KV head's eight query heads at a time, so that the scores fit
the host). It reads the engine's OWN bfloat16 weights, layer by layer,
and widens them to float32. Per layer, with ``x [T, D]`` the residual
rows of one sequence and ``N(u) = u / sqrt(mean(u^2) + 1e-5) w``:

- ``x0 = E[token]``; ``h = x + Mix(N1(x))``, ``x' = h + MoE(N2(h))``;
  ``logits = W_head N_f(x_L)``.
- softmax layer (``gqa_layers``): ``[q | k | v | g] = W_qkvg u`` (64 / 8 /
  8 heads of 128 and an elementwise gate of 8192), no rotation, no
  per-head norm, scores x 128^-0.5, causal softmax over every key 0..t,
  KV head j serves query heads 8j..8j+7;
  ``Mix = W_o [softmax(q k^T) v sigmoid(g)]``.
- KDA layer: ``[q|k|v] = SiLU(conv4(W_qkv u))`` (depthwise, causal, no
  bias), q and k divided by ``sqrt(sum x^2 + 1e-6)`` a head, q x
  128^-0.5; ``beta = 2 sigmoid(W_b u)`` a head; ``g = -exp(A_log)
  softplus(W_f2 W_f1 u + dt_bias)`` a channel, NO lower bound;
  ``S <- Diag(e^g) S; S <- S + beta k (v - S^T k)^T; o = S^T q``;
  ``Mix = W_o [N_128(o) sigmoid(W_g2 W_g1 u)]``. The engine holds
  ``[W_b | W_f1 | W_g1]`` as one matrix ``wbfg``; the reference splits it.
- MoE: ``s = sigmoid(W_r h)``, ``T = top8(s + b)``, ``g_e = s_e / sum_T
  s`` (scaling 1), output ``Shared(h) + sum_T g_e E_e(h)`` over the HELD
  experts, every expert a SwiGLU of 1280 with no clamp: the router and
  the expert loop of ``perfbench/arch/glm5next.py`` (the same equations
  under the same keys).

**The hit path** (``engine_prefill_logits``): the harness decodes every
reference prompt through the engine once, and its prompts share nothing,
so none would enter through the prefix store. Before anything else this
adapter serves each ``served_only`` prompt (the harness's own: the same
seed and lengths) for ONE token, so that the harness's decode of those
two restores a saved state at 1,536 and 3,072 tokens, prefills only the
tail, and is compared like any other.

``TOLERANCE``: the two readings it sits between are written beside it.

**Bytes and operations** of a decode step (``decode_step_bytes``,
``decode_step_flops``), of the grouped matmul (``expert_bytes``), of the
delta rule's step, of the page read and of the state copy are counted
here, so that no PR which claims a gain can change the count.
"""
from __future__ import annotations

import contextlib
import math
import threading
import time
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from perfbench.arch import glm5next as _shared  # the expert equations and the span helpers: the same under the same keys

# The two readings (PERF.md section 6, PR 44; my chip runs, one TPU v5 lite), prompts of
# 64, 96, 640 and 2,560 tokens, published widths, four layers, 40 of 320 experts held:
# - the engine's SERVED walks on the chip against this float32 reference, through the
#   compiled kernels: prefill_rel_err 0.0223 (one chunk), 0.0596 (95 tokens and one decode
#   step through the delta-rule kernel, the page kernel and the grouped matmul), 0.0270
#   (two extend chunks), 0.0351 (five chunks); the served tokens' margin 0.0064 over 48
#   tokens through the engine's own executables, the two prompts that entered through a
#   restored state among them. Prompts and weights are fixed, so the numbers repeat to the
#   digit (four runs). The 96-token prompt reads 0.0606 when ALL of it goes through one
#   extend chunk: the reading is that prompt's, not the decode step's.
# - the control one precision down (``precision="bfloat16"``: nothing in float32, the
#   recurrent state, the residual row, the norms, the softmax and the router included)
#   against the same reference, on the chip machine's host CPU with the CHIP's draws of the
#   weights (the engine's initialiser, seed 0, read back): prefill_rel_err 0.1143, 0.0801,
#   0.0797, 0.0511. It is NOT correct by prefill_rel_err, by that limit alone, and by ONE
#   prompt, the 64-token one.
# 0.083 is the geometric mean of the served walks' largest reading (0.0596) and the
# control's largest (0.1143): 1.39 above the one, 1.38 below the other. ONE thing rounded
# to bfloat16 in the float32 reference reads inside the limit: the experts' and router's
# input alone 0.0300 / 0.0057 / 0.0232 / 0.0094 (a top 8 that flipped where it reads
# 0.02-0.03: with 40 of 320 held a flip moves a held pair in or out), the recurrent state
# alone 0.0073 / 0.0066 / 0.0196 / 0.0096: prompt by prompt the served set and the
# control overlap, as they do for the other three expert configurations (PERF.md
# section 7, Opened by PR 44 (e)).
TOLERANCE = 0.083

HARNESS_PROMPT_SEED = 20240924  # perfbench/launcher.py ``reference_check``'s

_PENDING: List[Any] = []  # the deferred walks of the last engine_prefill_logits call
_REGISTERED: Dict[str, Any] = {}  # the configuration ``register`` was given
Deferred = _shared.Deferred


# --------------------------------------------------------------------------- #
# The engine's side


def layer_kinds(cfg: dict) -> List[str]:
    """'full' (softmax) | 'kda' of each layer SERVED, from the published list."""
    return ["full" if l in cfg["gqa_layers"] else "kda" for l in cfg["layers_served"]]


def model_config(cfg: dict):
    from generativeaiexamples_tpu.models.solaropen2 import SolarOpen2Config

    lin = cfg["linear_attn_config"]
    return SolarOpen2Config(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"], intermediate_size=cfg["intermediate_size"],
        num_hidden_layers=cfg["num_hidden_layers"], gqa_layers=tuple(cfg["gqa_layers"]),
        layers_served=tuple(cfg["layers_served"]), moe_intermediate_size=cfg["moe_intermediate_size"],
        n_routed_experts=cfg["n_routed_experts"], num_experts_per_tok=cfg["num_experts_per_tok"],
        experts_first=cfg["experts_first"], experts_held=cfg["n_routed_experts_held"],
        routed_scaling_factor=float(cfg["routed_scaling_factor"]), num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"], kda_num_heads=lin["num_heads"],
        kda_head_dim=lin["head_dim"], kda_conv=lin["short_conv_kernel_size"], kda_rank=cfg["kda_low_rank"],
        norm_eps=float(cfg["rms_norm_eps"]), max_seq_len=cfg["engine"]["max_seq_len"],
    )


def register(cfg: dict) -> None:
    from generativeaiexamples_tpu.models import registry

    registry.register_preset("solaropen2", cfg["name"], model_config(cfg))
    _REGISTERED["cfg"] = cfg


def served_only_prompts(eng, cfg: dict) -> List[List[int]]:
    """The harness's ``served_only`` prompts, as ``perfbench/launcher.py``
    makes them: one generator over every length, stops replaced by 0."""
    from perfbench import reference

    ref = cfg["reference"]
    lengths = list(ref["prompt_tokens"]) + list(ref.get("served_only_prompt_tokens", []))
    usable = min(cfg["vocab_size"], getattr(eng.tokenizer, "vocab_size", cfg["vocab_size"]))
    stops = set(eng.tokenizer.stop_ids())
    prompts = reference.seeded_prompts(lengths, usable, seed=HARNESS_PROMPT_SEED)
    return [[t if t not in stops else 0 for t in p] for p in prompts[len(ref["prompt_tokens"]):]]


def prime_prefix_store(eng, cfg: dict) -> List[int]:
    """Serve each ``served_only`` prompt for one token, so that the
    harness's own decode of it enters through a saved state. Returns the
    depth saved for each (0: the store is off or the prompt too short)."""
    from generativeaiexamples_tpu.engine.llm_engine import SamplingParams

    if getattr(eng, "_prefix", None) is None:
        return []
    depths = []
    for p in served_only_prompts(eng, cfg):
        list(eng.iter_ids(p, SamplingParams(temperature=0.0, max_tokens=1), timeout=900))
        depths.append(eng._prefix.cacheable_len(len(p)))
    print(f"solaropen2: served-only prompts primed the prefix store at depths {depths}", flush=True)
    return depths


def engine_prefill_logits(eng, prompts, on_tpu: bool):
    """Last-prompt-position logits from the walks the engine SERVES with
    (its family's ``extend_paged``, ``decode_paged`` and ``head``, with
    the kernel paths it resolved), on the engine's weights, in the
    engine's shapes for one row, over a scratch cache of ONE slot that
    goes from prompt to prompt as the last one left it, so every
    admission has a former tenant's state to reset. By prompt:

    - longer than ``prefill_chunk``: chunked extend (KDA's state, the
      convolution tails and the pages carried from chunk to chunk), then
      the head;
    - the first of the others: one chunk from position 0;
    - every other one: all but its last token the same way, then ONE
      decode step on that token (the delta-rule step, the page kernel,
      the grouped matmul).

    First of all the ``served_only`` prompts prime the prefix store
    (``prime_prefix_store``). The rows are ``Deferred``: the walks run
    when the first is read (the launcher's greedy requests enter the
    queue first)."""
    del on_tpu
    if "cfg" in _REGISTERED:
        prime_prefix_store(eng, _REGISTERED["cfg"])
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


def attention(u, w: Dict[str, Any], cfg: Dict[str, Any]):
    """u [T, D] normed -> [T, D]: gated GQA over the whole sequence, no
    position term; one KV head's query heads at a time."""
    import jax
    import jax.numpy as jnp

    Hq, Hk, Dh = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    T, G = u.shape[0], Hq // Hk
    q, k, v, g = jnp.split(u @ w["wqkvg"], [Hq * Dh, (Hq + Hk) * Dh, (Hq + 2 * Hk) * Dh], axis=1)
    q = jnp.moveaxis(q.reshape(T, Hk, G, Dh), 1, 0)  # KV head j: query heads Gj..Gj+G-1
    k, v = jnp.moveaxis(k.reshape(T, Hk, Dh), 1, 0), jnp.moveaxis(v.reshape(T, Hk, Dh), 1, 0)
    seen = np.arange(T)[None, :] <= np.arange(T)[:, None]

    def group(qkv):
        qj, kj, vj = qkv  # [T, G, Dh], [T, Dh], [T, Dh]
        sc = jnp.einsum("tgd,sd->gts", qj, kj) * Dh ** -0.5
        p = jax.nn.softmax(jnp.where(seen[None], sc.astype(jnp.float32), -jnp.inf), axis=-1).astype(u.dtype)
        return jnp.einsum("gts,sd->tgd", p, vj)

    o = jnp.moveaxis(jax.lax.map(group, (q, k, v)), 0, 1).reshape(T, Hq * Dh)
    return (o * jax.nn.sigmoid(g)) @ w["wo"]


def kda_mixer(u, w: Dict[str, Any], cfg: Dict[str, Any], state_dtype=None):
    """u [T, D] normed -> [T, D]: the delta rule with per-channel decay,
    token by token; ``beta`` in (0, 2), no bound on the decay.
    ``state_dtype``: what the state is kept in between tokens (None: as
    the rest; a test rounds it to bfloat16 alone)."""
    import jax
    import jax.numpy as jnp

    lin = cfg["linear_attn_config"]
    H, Dk, r, kc = lin["num_heads"], lin["head_dim"], cfg["kda_low_rank"], lin["short_conv_kernel_size"]
    T, K = u.shape[0], lin["num_heads"] * lin["head_dim"]
    proj = u @ w["wqkv"]
    padded = jnp.concatenate([jnp.zeros((kc - 1, proj.shape[1]), proj.dtype), proj], axis=0)
    qkv = sum(padded[i:i + T] * w["conv_w"][i] for i in range(kc))
    qkv = qkv * jax.nn.sigmoid(qkv)
    q, k, v = (qkv[:, j * K:(j + 1) * K].reshape(T, H, Dk) for j in range(3))
    q = q / jnp.sqrt(jnp.sum(q * q, axis=-1, keepdims=True) + 1e-6) * Dk ** -0.5
    k = k / jnp.sqrt(jnp.sum(k * k, axis=-1, keepdims=True) + 1e-6)
    small = u @ w["wbfg"]
    beta = 2.0 * jax.nn.sigmoid(small[:, :H])
    f = small[:, H:H + r] @ w["wf2"] + w["dt_bias"]
    g = -jnp.exp(w["A_log"])[None, :, None] * jax.nn.softplus(f).reshape(T, H, Dk)
    gate = jax.nn.sigmoid(small[:, H + r:] @ w["wg2"])
    sdt = state_dtype or u.dtype

    def step(S, inp):
        q_t, k_t, v_t, b_t, g_t = inp
        S = jnp.exp(g_t)[:, :, None] * S.astype(u.dtype)
        S = S + b_t[:, None, None] * k_t[:, :, None] * (v_t - jnp.einsum("hkv,hk->hv", S, k_t))[:, None, :]
        return S.astype(sdt), jnp.einsum("hkv,hk->hv", S, q_t).astype(u.dtype)

    _, o = jax.lax.scan(step, jnp.zeros((H, Dk, Dk), sdt), (q, k, v, beta, g))
    o = rms(o, w["o_norm"], cfg["rms_norm_eps"])
    return (o.reshape(T, K) * gate) @ w["wo"]


def expert_keys(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The expert layer's numbers under the keys ``perfbench/arch/glm5next.py`` reads."""
    return {"swiglu_limit": math.inf, "num_experts_per_tok": cfg["num_experts_per_tok"],
            "routed_scaling_factor": float(cfg["routed_scaling_factor"]), "experts_first": cfg["experts_first"],
            "n_routed_experts_held": cfg["n_routed_experts_held"]}


moe = _shared.moe


def layer_functions(cfg: Dict[str, Any], state_dtype=None, router_dtype=None) -> Dict[str, Any]:
    """One layer's pieces, each compiled once a sequence length: the two
    mixers with their norm and residual, and the norm before the experts
    (whose loop follows the routing, outside any compiled program).
    ``router_dtype``: the experts' (and so the router's) input rounded to
    it (a test's fault)."""
    import jax

    eps = cfg["rms_norm_eps"]

    def read(x, w):
        u = rms(x, w["ln_mlp"], eps)
        return u if router_dtype is None else u.astype(router_dtype).astype(u.dtype)

    return {
        "full": jax.jit(lambda x, w: x + attention(rms(x, w["ln_mix"], eps), w, cfg)),
        "kda": jax.jit(lambda x, w: x + kda_mixer(rms(x, w["ln_mix"], eps), w, cfg, state_dtype)),
        "read": jax.jit(read),
        "add_expert": jax.jit(lambda y, x, pad, gate, wg, wd: _shared._add_expert(y, x, pad, gate, wg, wd, math.inf),
                              donate_argnums=(0,)),
    }


_EXPERT_LEAVES = ("we_gate_up", "we_down")


def forward(tokens_list: Sequence[Sequence[int]], cfg: Dict[str, Any], embed, layer_weights, expert_weights,
            final, positions: int, device=None, precision: str = "float32",
            state_dtype=None, router_dtype=None) -> List[np.ndarray]:
    """Logits [T, vocab] per sequence, computed at the last ``positions``
    positions (the rest stays zero: only those rows are compared). Each
    layer's weights are fetched once (``layer_weights(l)``: a dict;
    ``expert_weights(l)``: the held experts' two stacked leaves), applied
    to all sequences, then dropped. ``final`` is (norm weight, head).
    ``precision="bfloat16"`` is the control one precision down: nothing
    in float32, the recurrent state, the residual row, the norms and the
    router included."""
    import jax
    import jax.numpy as jnp

    ctx = jax.default_device(device) if device is not None else contextlib.nullcontext()
    dt = jnp.float32 if precision == "float32" else jnp.bfloat16
    kinds, ek = layer_kinds(cfg), expert_keys(cfg)
    t0 = time.time()
    with ctx, jax.default_matmul_precision("highest"):
        fns = layer_functions(cfg, state_dtype, router_dtype)
        cast = lambda a: jnp.asarray(a).astype(dt)  # noqa: E731
        emb = cast(embed)
        # every mixer is causal, so zeros after a sequence change nothing before
        # them: lengths are rounded up to whole 128s and sequences of one
        # rounded length share their compiled pieces
        padded = [list(t) + [0] * (-len(t) % 128) for t in tokens_list]
        xs = [emb[np.asarray(t)] for t in padded]
        for l, mixer in enumerate(kinds):
            w = {k: cast(v) for k, v in layer_weights(l).items()}
            for i, x in enumerate(xs):
                x = fns[mixer](x, w)
                if l == len(kinds) - 1:
                    # the last layer's experts mix no positions: the compared ones only
                    x = x[-(positions + len(padded[i]) - len(tokens_list[i])):]
                xs[i] = x
            # the experts see the rows of every sequence at once (a token's MLP reads
            # no other token), so each held expert's matrices are fetched once a layer
            us = [fns["read"](x, w) for x in xs]
            held = expert_weights(l)
            y = moe(jnp.concatenate(us), w, ek, lambda e: tuple(cast(a[e]) for a in held), fns["add_expert"])
            del held
            ends = np.cumsum([u.shape[0] for u in us])
            xs = [(x + y[end - x.shape[0]:end]).astype(dt) for x, end in zip(xs, ends)]
            jax.block_until_ready(xs)
            del w
            print(f"solaropen2 reference ({precision}): layer {l} ({mixer}) of {len(tokens_list)} sequences "
                  f"done {time.time() - t0:.1f} s in", flush=True)
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
                     device=None, precision: str = "float32", **faults) -> List[np.ndarray]:
    """``forward`` over the engine's own parameter tree; the engine's
    served walks (deferred) run on the chip meanwhile."""
    del tp  # one device serves this share
    print(f"solaropen2 reference ({precision}): starts; the launcher's greedy requests are done", flush=True)
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
            positions=int(cfg["reference"]["decode_tokens"]) + 1, device=device, precision=precision, **faults,
        )
    finally:
        if served is not None:
            served.join()


# --------------------------------------------------------------------------- #
# Bytes and operations of a decode step and of its kernels


def _sizes(cfg: Dict[str, Any]) -> Dict[str, float]:
    lin = cfg["linear_attn_config"]
    D, Dh = cfg["hidden_size"], cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * Dh, cfg["num_key_value_heads"] * Dh
    K, r, H = lin["num_heads"] * lin["head_dim"], cfg["kda_low_rank"], lin["num_heads"]
    kinds = layer_kinds(cfg)
    return {
        "D": D, "q": q, "kv": kv, "K": K, "n": len(kinds),
        "attn": D * (2 * q + 2 * kv) + q * D,  # bfloat16 elements
        "kda": D * 3 * K + D * (H + 2 * r) + 2 * r * K + K * D,
        "kda_f32": lin["short_conv_kernel_size"] * 3 * K + K + H + lin["head_dim"],
        "norms_f32": 2 * D,
        "shared": 3 * D * cfg["moe_intermediate_size"],
        "router_f32": D * cfg["n_routed_experts"] + cfg["n_routed_experts"],
        "expert": 3 * D * cfg["moe_intermediate_size"],
        "n_full": sum(1 for m in kinds if m == "full"), "n_kda": sum(1 for m in kinds if m == "kda"),
        # one row's recurrent state a KDA layer: float32 [H, Dk, Dk] (the step kernel
        # moves nothing else of size) and the convolution's bfloat16 tail
        "state": H * lin["head_dim"] ** 2 * 4,
        "tail": (lin["short_conv_kernel_size"] - 1) * 3 * K * 2,
    }


def expert_bytes(cfg: Dict[str, Any]) -> int:
    """bfloat16 bytes of ONE routed expert's three matrices (31,457,280 at the published widths)."""
    return int(2 * _sizes(cfg)["expert"])


def state_row_bytes(cfg: Dict[str, Any]) -> int:
    """Bytes of one row of every fixed-state leaf: what a prefix save or
    restore copies (13,025,280 at the published widths)."""
    s = _sizes(cfg)
    return int(s["n_kda"] * (s["state"] + s["tail"]))


def page_bytes(cfg: Dict[str, Any]) -> int:
    """bfloat16 bytes of one page of the ONE paged layer, K and V (524,288)."""
    return int(cfg["engine"]["page_size"] * 2 * _sizes(cfg)["kv"] * 2)


def fixed_weight_bytes(cfg: Dict[str, Any]) -> float:
    """Weights a decode step reads whatever it routes: everything outside
    the routed experts, and the head over the held vocabulary."""
    s = _sizes(cfg)
    bf16 = s["n_full"] * s["attn"] + s["n_kda"] * s["kda"] + s["n"] * s["shared"]
    f32 = s["n_kda"] * s["kda_f32"] + s["n"] * (s["norms_f32"] + s["router_f32"]) + s["D"]
    return 2.0 * (bf16 + s["D"] * cfg["vocab_size"]) + 4.0 * f32


def expected_experts_hit(cfg: Dict[str, Any], rows: float) -> float:
    """Held experts a step of ``rows`` tokens reaches, summed over the
    layers, under a uniform router: ``held (1 - (1 - k/E)^rows)``."""
    p = 1.0 - (1.0 - cfg["num_experts_per_tok"] / cfg["n_routed_experts"]) ** max(rows, 0.0)
    return _sizes(cfg)["n"] * cfg["n_routed_experts_held"] * p


def decode_step_bytes(cfg: Dict[str, Any], rows: float, mean_context: float,
                      experts_hit: Optional[float] = None, full_tokens: Optional[float] = None) -> float:
    """HBM bytes one decode step of ``rows`` sequences must move: the
    fixed weights once; the matrices of the experts HIT (summed over the
    layers: measured where the spans give it, else the uniform router's
    expectation); per row KDA's state in and out and the tail in and
    out; the cached tokens the softmax layer read (K and V of 8 heads of
    128: measured where the spans give them, else from the mean
    context); per row the new K/V rows and an embedding row."""
    s = _sizes(cfg)
    hit = expected_experts_hit(cfg, rows) if experts_hit is None else experts_hit
    if full_tokens is None:
        full_tokens = rows * s["n_full"] * (mean_context + 1)
    token_bytes = 2 * 2 * s["kv"]  # K and V, bfloat16
    per_row = 2.0 * s["n_kda"] * (s["state"] + s["tail"]) + s["n_full"] * token_bytes + 2 * s["D"]
    return fixed_weight_bytes(cfg) + hit * expert_bytes(cfg) + full_tokens * token_bytes + rows * per_row


def decode_step_flops(cfg: Dict[str, Any], rows: float, mean_context: float) -> float:
    """Multiply-adds x 2 a step: every fixed matrix once a row, the held
    share of a row's 8 experts, scores and values of 64 heads over the
    context (one layer), the delta rule over each state element three
    times (three layers)."""
    s = _sizes(cfg)
    held = cfg["num_experts_per_tok"] * cfg["n_routed_experts_held"] / cfg["n_routed_experts"]
    fixed = (s["n_full"] * s["attn"] + s["n_kda"] * s["kda"]
             + s["n"] * (s["shared"] + s["router_f32"] + held * s["expert"]) + s["D"] * cfg["vocab_size"])
    attn = s["n_full"] * 2 * s["q"] * (mean_context + 1)
    state = s["n_kda"] * 3 * s["state"] / 4
    return 2.0 * rows * (fixed + attn + state)


def decode_step_floor_s(cfg: Dict[str, Any], peaks: Dict[str, float], rows: float, mean_context: float,
                        experts_hit: Optional[float] = None, full_tokens: Optional[float] = None) -> float:
    t_bytes = decode_step_bytes(cfg, rows, mean_context, experts_hit, full_tokens) / peaks["hbm_bytes_per_s"]
    t_flops = decode_step_flops(cfg, rows, mean_context) / peaks["bf16_flops_per_s"]
    return max(t_bytes, t_flops)


# --------------------------------------------------------------------------- #
# Readers of this architecture's own spans and counters


def _decode_steps_traced(ctx) -> float:
    block = float(ctx["config"]["engine"].get("decode_block", 1) or 1)
    return _shared._programs_traced(ctx["trace"], r"^jit_decode") * block


def _kernel_self_s(ctx, params) -> Optional[float]:
    from perfbench import trace_reduce

    tr = ctx["trace"]
    if not tr or not tr.get("devices"):
        return None
    return trace_reduce.matching_s(tr["ops_self_s"], params["match"]) or None


def decode_roofline_share(ctx, params) -> Optional[float]:
    """``decode_step_floor_s`` with the experts HIT and the softmax
    layer's tokens READ a step that the decode spans report, over the
    measured device time of a step, percent."""
    from perfbench import readers

    step_ms = ctx["read"](params["time_metric"])
    rows = readers.span_mean(ctx, {"kind": "decode", "field": "rows"})
    hit = readers.span_mean(ctx, {"kind": "decode", "field": "moe_experts_hit"})
    full = readers.span_mean(ctx, {"kind": "decode", "field": "full_tokens_read"})
    if not step_ms or not rows or hit is None or full is None:
        return None
    context = full / max(1, _sizes(ctx["config"])["n_full"]) / rows
    floor_s = decode_step_floor_s(ctx["config"], ctx["peaks"], rows, context, hit, full)
    return 100.0 * floor_s / (step_ms / 1000.0)


def grouped_matmul_roofline_share(ctx, params) -> Optional[float]:
    """Bytes of the experts HIT in the traced interval over the HBM peak,
    over the grouped-matmul kernels' self time there, percent. Bytes: the
    programs the trace counted (decode blocks of ``decode_block`` steps,
    extend chunks) times the experts a step / a chunk hit in the window's
    spans, times an expert's three matrices."""
    from perfbench import readers

    self_s = _kernel_self_s(ctx, params)
    hit_step = readers.span_mean(ctx, {"kind": "decode", "field": "moe_experts_hit"})
    if not self_s or hit_step is None:
        return None
    hit_chunk = readers.span_mean(ctx, {"kind": "prefill_chunk", "field": "moe_experts_hit"}) or 0.0
    hits = _decode_steps_traced(ctx) * hit_step + _shared._programs_traced(ctx["trace"], r"^jit_extend") * hit_chunk
    return 100.0 * hits * expert_bytes(ctx["config"]) / ctx["peaks"]["hbm_bytes_per_s"] / self_s


def delta_step_roofline_share(ctx, params) -> Optional[float]:
    """The states the step kernel moved in the traced interval, in and
    out (2 x rows x 64 heads x 65,536 B x 3 layers a step: PR 39's
    formula), over the HBM peak, over the kernel's self time there,
    percent. Rows: ``state_kernel_rows`` of the window's decode spans."""
    from perfbench import readers

    self_s = _kernel_self_s(ctx, params)
    rows = readers.span_mean(ctx, {"kind": "decode", "field": "state_kernel_rows"})
    if not self_s or not rows:
        return None
    s = _sizes(ctx["config"])
    moved = _decode_steps_traced(ctx) * rows * 2.0 * s["state"] * s["n_kda"]
    return 100.0 * moved / ctx["peaks"]["hbm_bytes_per_s"] / self_s


def page_attn_roofline_share(ctx, params) -> Optional[float]:
    """Bytes of the pages the page kernel walked in the traced interval
    (K and V of a page, once) over the HBM peak, over the kernel's self
    time there, percent. Pages: the decode steps the trace counted times
    the pages a step walked in the window's spans (``kv_pages_walked``)."""
    from perfbench import readers

    self_s = _kernel_self_s(ctx, params)
    pages = readers.span_mean(ctx, {"kind": "decode", "field": "kv_pages_walked"})
    if not self_s or pages is None:
        return None
    return (100.0 * _decode_steps_traced(ctx) * pages * page_bytes(ctx["config"])
            / ctx["peaks"]["hbm_bytes_per_s"] / self_s)


def _copy_programs(ctx, params):
    import re

    tr = ctx["trace"]
    if not tr or not tr.get("devices"):
        return None
    rx = re.compile(params["match"])
    hits = [m for name, m in tr["modules"].items() if rx.search(name)]
    count, total_s = sum(m["count"] for m in hits), sum(m["total_s"] for m in hits)
    return (count, total_s) if count and total_s else None


def prefix_state_copy_roofline_share(ctx, params) -> Optional[float]:
    """A prefix save or restore reads one row of every fixed-state leaf
    and writes one: 2 x ``state_row_bytes`` a program the trace counted,
    over the HBM peak, over those programs' device time, percent."""
    got = _copy_programs(ctx, params)
    if got is None:
        return None
    count, total_s = got
    return 100.0 * count * 2.0 * state_row_bytes(ctx["config"]) / ctx["peaks"]["hbm_bytes_per_s"] / total_s


def prefix_state_copy_device_share(ctx, params) -> Optional[float]:
    """The state copies' device time over device-busy time of the traced interval, percent."""
    got = _copy_programs(ctx, params)
    if got is None or not ctx["trace"].get("busy_s"):
        return None
    return 100.0 * got[1] / ctx["trace"]["busy_s"]


def prefix_reused_token_share(ctx, params) -> Optional[float]:
    """Prompt tokens served from the prefix store over prompt tokens
    submitted, in the window, percent: the store's reused-token counter
    over itself plus the tokens the prefill and extend programs
    computed. A parent without the counters, or a store that is off
    (nothing reused, nothing counted), gives nothing to read."""
    from perfbench import readers

    del params
    grew = lambda name: (readers.metric_sum(ctx["metrics_after"], name)  # noqa: E731
                         - readers.metric_sum(ctx["metrics_before"], name))
    reused = grew("genai_engine_prefix_cache_tokens_reused_total")
    computed = grew("genai_engine_prefill_tokens_total")
    if not any(n == "genai_engine_prefix_state_restores_total" for n, _ in ctx["metrics_after"]):
        return None
    return 100.0 * reused / (reused + computed) if reused + computed > 0 else None
