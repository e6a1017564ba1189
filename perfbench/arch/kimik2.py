"""The adapter of Kimi-K2.5 (``kimi_k2``, 1.04T-A32B) as one chip's
share of a 32-way expert-parallel deployment (contract:
``perfbench/arch/__init__.py``). The language model on text: the
catalog gives the vision tower no sizes, and it is not served.

**Registration.** ``register`` writes the configuration file's published
widths, the layers served and the chip's share (12 of 384 routed experts
from expert 0 on, 20,480 vocabulary rows) into the program's model
registry under the configuration's name, as a configuration of the
``kimik2`` family.

**The plain reference**: float32 ``jax.numpy`` written from the layer
equations of ISSUE 49 (DeepSeek-V3's layer), importing nothing of the
program's models: no kernel, no cache, no batching, no absorbed form. It
reads the engine's OWN bfloat16 weights, layer by layer, and widens them
to float32. It is given the same share as the engine: the router scores
all 384 experts and keeps 8 a token, only the pairs whose expert is held
(plus the shared expert) are computed, the head covers the held
vocabulary rows. The router and the expert loop are the functions of
``perfbench/arch/glm5next.py``, YaRN's frequencies and the interleaved
rotation those of ``perfbench/arch/gigachat35.py`` (the same equations
under the same keys). Per layer, with ``x [T, D]`` the residual rows of
one sequence and ``N(u) = u / sqrt(mean(u^2) + 1e-5) w``:

- ``h = x + Attn(N1(x))``, ``x' = h + MLP(N2(h))``; ``logits = W_head
  N_f(x_L)``.
- latent attention, UNABSORBED, ``a = N1(x)``: ``cq = N(W_dq a)``,
  ``[q_nope | q_rope] = W_uq cq`` (64 heads of 128 | 64), ``[c_kv | k_r] =
  W_dkv a``, ``c = N(c_kv)``, ``k_rope = RoPE(k_r)`` shared by the heads,
  ``q_rope = RoPE(q_rope)`` (interleaved pairs, theta 50000, YaRN factor
  64 over 4096, beta 32 / 1, cos and sin unscaled); ``k_h,s = [W_uk,h c_s
  | k_rope,s]``, ``v_h,s = W_uv,h c_s``; causal softmax of ``q_h . k_h,s *
  192^-0.5 (0.1 ln 64 + 1)^2`` over ALL s <= t; ``W_o concat_h(o_h)``; no
  gate, no bias. Computed a block of QUERIES at a time against the keys up to
  the block's group (an exact softmax with no running maximum), the last
  layer for the compared rows alone, so that 16,640 tokens fit the host
  and the comparison ends beside the ramp.
- layer 0: ``W_down(SiLU(W_gate u) * W_up u)`` at 18432. Every other
  layer: ``s = sigmoid(W_r u)``, ``T = top8(s + e_bias)``, ``g_e = 2.827
  s_e / sum_T s``, an expert the same SwiGLU at 2048 with no clamp; the
  shared expert added once, ungated.

**The hit path** (``engine_prefill_logits``): the harness decodes every
reference prompt through the engine once, and its prompts share nothing,
so none would enter through the prefix store. Before anything else this
adapter serves each ``served_only`` prompt (the harness's own: the same
seed and lengths) for ONE token, so that the harness's decode of it maps
the entry's shared latent pages (4,096 tokens of them at 4,352),
prefills only the tail through the expanded read at that offset, and is
compared like any other.

``TOLERANCE``: the two readings it sits between are written beside it.

**Bytes and operations** of a decode step (``decode_step_bytes``,
``decode_step_flops``), of the grouped matmul (``expert_bytes``) and of
the latent read (``latent_read_bytes_and_flops``) are counted here, so
that no PR which claims a gain can change the count.
"""
from __future__ import annotations

import contextlib
import math
import os
import tempfile
import threading
import time
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from perfbench.arch import gigachat35 as _mla  # YaRN's frequencies, the rotation, the softmax scale: the same keys
from perfbench.arch import glm5next as _shared  # the expert equations and the span helpers: the same under the same keys

# The two readings (PERF.md section 6, PR 49; my chip runs, one TPU v5 lite), prompts of
# 64, 96, 640 and 2,560 tokens, published widths, five layers, 12 of 384 experts held:
# - the engine's SERVED walks on the chip against this float32 reference, through the
#   compiled kernels: prefill_rel_err 0.0184 (one chunk), 0.0260 (95 tokens and one decode
#   step through the five absorbed latent reads and the grouped matmul), 0.0468 (two extend
#   chunks), 0.0266 (five chunks); the served tokens' margin 0.00085 over 40 tokens through
#   the engine's own executables, the 4,352-token prompt that entered through 4,096 tokens
#   of shared latent pages among them. Prompts and weights are fixed, so the numbers repeat
#   to the digit (seven runs and ``python3 -m perfbench.arch.kimik2 --control``).
# - the control one precision down (``precision="bfloat16"``: nothing in float32, the
#   residual row, the norms, the softmax and the router included) against the same
#   reference, on the chip machine's host CPU with the CHIP's draws of the weights (the
#   engine's initialiser, seed 0, read back) and the harness's own prompts:
#   prefill_rel_err 0.0492, 0.0584, 0.0729, 0.0478. It is NOT correct by prefill_rel_err,
#   by that limit alone, and by ONE prompt, the 640-token one.
# 0.058 is the geometric mean of the served walks' largest reading (0.0468) and the
# control's largest (0.0729): 1.25 above the one, 1.25 below the other. Prompt by prompt
# the two sets do not overlap here (every control reading lies above every served one),
# which no other expert configuration can say. Two things moved these readings and are
# worth knowing (PERF.md section 6): with ``W_uk`` drawn at 1/sqrt(128) in place of its
# fan-in's 1/sqrt(512) the scores' spread was 3.5 and the served walks read 0.053 / 0.050
# / 0.103 / 0.093 (control 0.117-0.205); and prompts drawn over 260 token ids in place of
# the harness's 20,480 (the byte tokenizer of an engine built without the harness's file)
# put the served tokens at ranks 18-80 of the reference at 16,640 tokens.
TOLERANCE = 0.058

HARNESS_PROMPT_SEED = 20240924  # perfbench/launcher.py ``reference_check``'s
QUERY_BLOCK = 512  # query rows of the reference's attention a block

_PENDING: List[Any] = []  # the deferred walks of the last engine_prefill_logits call
_REGISTERED: Dict[str, Any] = {}  # the configuration ``register`` was given
Deferred = _shared.Deferred


# --------------------------------------------------------------------------- #
# The engine's side


def layer_kinds(cfg: dict) -> List[str]:
    """'dense' | 'sparse': the MLP of each layer SERVED, from the published keys."""
    return ["dense" if l < cfg["first_k_dense_replace"] else "sparse" for l in cfg["layers_served"]]


def model_config(cfg: dict):
    from generativeaiexamples_tpu.models.kimik2 import KimiK2Config

    rs = cfg["rope_scaling"]
    return KimiK2Config(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_hidden_layers=cfg["num_hidden_layers"], layers_served=tuple(cfg["layers_served"]),
        first_k_dense_replace=cfg["first_k_dense_replace"], intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"], n_routed_experts=cfg["n_routed_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"], experts_first=cfg["experts_first"],
        experts_held=cfg["n_routed_experts_held"], routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        num_heads=cfg["num_attention_heads"], q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"], qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"], rope_theta=float(cfg["rope_theta"]), rope_factor=float(rs["factor"]),
        rope_original_max=rs["original_max_position_embeddings"], rope_beta_fast=float(rs["beta_fast"]),
        rope_beta_slow=float(rs["beta_slow"]), rope_mscale=float(rs["mscale"]),
        rope_mscale_all_dim=float(rs["mscale_all_dim"]), norm_eps=float(cfg["rms_norm_eps"]),
        max_seq_len=cfg["engine"]["max_seq_len"],
    )


def register(cfg: dict) -> None:
    from generativeaiexamples_tpu.models import registry

    registry.register_preset("kimik2", cfg["name"], model_config(cfg))
    _REGISTERED["cfg"] = cfg


def _harness_prompts(eng, cfg: dict, lengths: Sequence[int], seed: int) -> List[List[int]]:
    """Seeded prompts as ``perfbench/launcher.py`` makes them: one generator
    over every length, stops replaced by 0."""
    from perfbench import reference

    usable = min(cfg["vocab_size"], getattr(eng.tokenizer, "vocab_size", cfg["vocab_size"]))
    stops = set(eng.tokenizer.stop_ids())
    return [[t if t not in stops else 0 for t in p] for p in reference.seeded_prompts(lengths, usable, seed=seed)]


def served_only_prompts(eng, cfg: dict) -> List[List[int]]:
    """The harness's ``served_only`` prompts (its seed, its lengths)."""
    ref = cfg["reference"]
    lengths = list(ref["prompt_tokens"]) + list(ref.get("served_only_prompt_tokens", []))
    return _harness_prompts(eng, cfg, lengths, HARNESS_PROMPT_SEED)[len(ref["prompt_tokens"]):]


def prime_prefix_store(eng, prompts: Sequence[Sequence[int]]) -> List[int]:
    """Serve each prompt for one token, so that its next admission enters
    through the store entry that leaves. Returns the depth cached for
    each ([] where the store is off)."""
    from generativeaiexamples_tpu.engine.llm_engine import SamplingParams

    if getattr(eng, "_prefix", None) is None:
        return []
    depths = []
    for p in prompts:
        list(eng.iter_ids(list(p), SamplingParams(temperature=0.0, max_tokens=1), timeout=900))
        depths.append(eng._prefix.cacheable_len(len(p)))
    print(f"kimik2: served-only prompts primed the prefix store at depths {depths}", flush=True)
    return depths


def engine_prefill_logits(eng, prompts, on_tpu: bool):
    """Last-prompt-position logits from the walks the engine SERVES with
    (its family's ``extend_paged``, ``decode_paged`` and ``head``, with
    the kernel paths it resolved), on the engine's weights, in the
    engine's shapes for one row, over a scratch cache of ONE row's pages
    that goes from prompt to prompt as the last one left it. By prompt:

    - longer than ``prefill_chunk``: chunked extend (the pages carried
      from chunk to chunk; the expanded latent read at offsets past one
      chunk), then the head;
    - the first of the others: one chunk from position 0;
    - every other one: all but its last token the same way, then ONE
      decode step on that token (the absorbed latent read through the
      page kernel, five pools, and the grouped matmul).

    First of all the ``served_only`` prompts prime the prefix store
    (``prime_prefix_store``). The rows are ``Deferred``: the walks run
    when the first is read (the launcher's greedy requests enter the
    queue first)."""
    del on_tpu
    if "cfg" in _REGISTERED:
        prime_prefix_store(eng, served_only_prompts(eng, _REGISTERED["cfg"]))
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
    caches = fam.init_paged_cache(cfg, 1 + pmax, page, 1, eng._cache["lat"][0].dtype)
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
# The plain float32 reference (imports nothing of the program's models)

yarn_mscale, yarn_inv_freq, softmax_scale = _mla.yarn_mscale, _mla.yarn_inv_freq, _mla.softmax_scale
rope_interleaved = _mla.rope_interleaved
route, moe = _shared.route, _shared.moe


def rms(u, w, eps: float):
    import jax.numpy as jnp

    return u / jnp.sqrt(jnp.mean(u * u, axis=-1, keepdims=True) + eps) * w


def swiglu(x, w_gate_up, w_down):
    """``W_down(SiLU(W_gate x) * W_up x)``, no clamp."""
    return _shared.swiglu(x, w_gate_up, w_down, math.inf)


def query_plan(T: int, query_block: int = QUERY_BLOCK, first_query: int = 0):
    """How ``mla_mixer`` walks T rows (a whole number of 128s, or fewer
    than 128): (block, [(first block, blocks, keys)] a group). A block
    is the largest whole number of 128s, at most ``query_block``, that
    divides T; the blocks from ``first_query`` on go in up to four
    groups, each reading the keys up to ITS last row and no further (a
    query reads no later key: what is skipped is exactly masked)."""
    unit = min(128, query_block)
    if T <= unit or T % unit:
        B = T
    else:
        B = unit * max(d for d in range(1, query_block // unit + 1) if (T // unit) % d == 0)
    nb, b0 = T // B, max(0, first_query) // B
    n = min(4, nb - b0)
    cuts = [b0 + (nb - b0) * g // n for g in range(n + 1)]
    return B, [(cuts[g], cuts[g + 1] - cuts[g], cuts[g + 1] * B) for g in range(n)]


def mla_inputs(u, positions, w: Dict[str, Any], cfg: Dict[str, Any]):
    """u [N, D] normed rows of ANY sequences with their positions [N] ->
    (q_nope [N, H, dn], q_rope [N, H, dr] rotated, k_nope [N, H, dn],
    k_rope [N, dr] rotated and shared by the heads, v [N, H, Dv]): every
    product of the mixer that reads one row alone."""
    import jax.numpy as jnp

    H, ql, R = cfg["num_attention_heads"], cfg["q_lora_rank"], cfg["kv_lora_rank"]
    dn, dr, eps = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["rms_norm_eps"]
    xp = u @ w["wx"]  # [cq | c_kv | k_r]
    cq = rms(xp[:, :ql], w["q_norm"], eps)
    c = rms(xp[:, ql:ql + R], w["kv_norm"], eps)
    k_rope = rope_interleaved(xp[:, ql + R:], positions, cfg)
    q = (cq @ w["wcq"]).reshape(u.shape[0], H, dn + dr)
    return (q[..., :dn], rope_interleaved(q[..., dn:], positions, cfg),
            jnp.einsum("sr,hdr->shd", c, w["wuk"]), k_rope, jnp.einsum("sr,hrv->shv", c, w["wuv"]))


def attention_core(q_nope, q_rope, k_nope, k_rope, v, cfg: Dict[str, Any], query_block: int = QUERY_BLOCK,
                   first_query: int = 0):
    """ONE sequence's causal softmax, rows 0..T-1 in order: [T - start, H
    * Dv] for the query rows from ``first_query`` (rounded down to a
    block's start) on. Queries a block at a time (``query_plan``), each
    with an exact softmax over the keys it can see."""
    import jax
    import jax.numpy as jnp

    T, H, dn = q_nope.shape
    dr, Dv = q_rope.shape[-1], v.shape[-1]
    B, groups = query_plan(T, query_block, first_query)
    scale = softmax_scale(cfg)
    out = []
    for b0, n, keys in groups:
        kn, kr, vv = k_nope[:keys], k_rope[:keys], v[:keys]

        def block(args, kn=kn, kr=kr, vv=vv, keys=keys):
            qn, qr, at = args  # [B, H, dn], [B, H, dr], [B] positions
            sc = (jnp.einsum("thd,shd->hts", qn, kn) + jnp.einsum("thd,sd->hts", qr, kr)) * scale
            seen = jnp.arange(keys)[None, :] <= at[:, None]
            p = jax.nn.softmax(jnp.where(seen[None], sc.astype(jnp.float32), -jnp.inf), axis=-1).astype(vv.dtype)
            return jnp.einsum("hts,shv->thv", p, vv)

        rows = slice(b0 * B, (b0 + n) * B)
        out.append(jax.lax.map(block, (q_nope[rows].reshape(n, B, H, dn), q_rope[rows].reshape(n, B, H, dr),
                                       jnp.arange(b0 * B, (b0 + n) * B).reshape(n, B))).reshape(n * B, H * Dv))
    return jnp.concatenate(out)


def mla_mixer(u, w: Dict[str, Any], cfg: Dict[str, Any], query_block: int = QUERY_BLOCK, first_query: int = 0):
    """u [T, D] normed rows of ONE sequence -> the mixer's output for the
    rows from ``first_query`` (rounded down to a block) on: latent
    attention unabsorbed, every earlier token read."""
    o = attention_core(*mla_inputs(u, np.arange(u.shape[0]), w, cfg), cfg, query_block, first_query)
    return o @ w["wo"]


def expert_keys(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The expert layer's numbers under the keys ``perfbench/arch/glm5next.py`` reads."""
    return {"swiglu_limit": math.inf, "num_experts_per_tok": cfg["num_experts_per_tok"],
            "routed_scaling_factor": float(cfg["routed_scaling_factor"]), "experts_first": cfg["experts_first"],
            "n_routed_experts_held": cfg["n_routed_experts_held"]}


def layer_functions(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """One layer's pieces. What reads one row alone (the projections, the
    output product with its residual, the dense MLP, the norm before the
    experts) runs over the rows of EVERY sequence at once, compiled once
    a run; the softmax is compiled a sequence length (a small program).
    The experts' loop follows the routing, outside any compiled program."""
    import jax

    eps = cfg["rms_norm_eps"]
    return {
        "inputs": jax.jit(lambda x, pos, w: mla_inputs(rms(x, w["ln_attn"], eps), pos, w, cfg)),
        "core": jax.jit(lambda qn, qr, kn, kr, v, first: attention_core(qn, qr, kn, kr, v, cfg, first_query=first),
                        static_argnums=(5,)),
        "output": jax.jit(lambda x, o, w: x + o @ w["wo"]),
        "dense": jax.jit(lambda x, w: x + swiglu(rms(x, w["ln_mlp"], eps), w["w_gate_up"], w["w_down"])),
        "read": jax.jit(lambda x, w: rms(x, w["ln_mlp"], eps)),
        "add_expert": jax.jit(lambda y, x, pad, gate, wg, wd: _shared._add_expert(y, x, pad, gate, wg, wd, math.inf),
                              donate_argnums=(0,)),
    }


_EXPERT_LEAVES = ("we_gate_up", "we_down")


def forward(tokens_list: Sequence[Sequence[int]], cfg: Dict[str, Any], embed, layer_weights, expert_weights,
            final, positions: int, device=None, precision: str = "float32") -> List[np.ndarray]:
    """Logits [T, vocab] per sequence, computed at the last ``positions``
    positions (the rest stays zero: only those rows are compared). Each
    layer's weights are fetched once (``layer_weights(l)``: a dict;
    ``expert_weights(l)``: the held experts' two stacked leaves, None
    for the dense layer), applied to all sequences, then dropped.
    ``final`` is (norm weight, head). ``precision="bfloat16"`` is the
    control one precision down: nothing in float32, the residual row,
    the norms, the softmax's output and the router included."""
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
        # attention is causal, so zeros after a sequence change nothing before
        # them: lengths are rounded up to whole 128s (sequences of one rounded
        # length share the softmax's program). The rows of all sequences lie one
        # after the other in ``x``; ``lens`` says where each sequence's are
        padded = [list(t) + [0] * (-len(t) % 128) for t in tokens_list]
        lens = [len(t) for t in padded]
        x = emb[np.concatenate([np.asarray(t) for t in padded])]
        pos = jnp.asarray(np.concatenate([np.arange(n) for n in lens]), jnp.int32)
        for l, mlp in enumerate(kinds):
            w = {k: cast(v) for k, v in layer_weights(l).items()}
            last = l == len(kinds) - 1
            parts = fns["inputs"](x, pos, w)
            outs, kept, at = [], [], 0
            for n, tokens in zip(lens, tokens_list):
                # the last layer mixes positions for the compared rows alone (its keys and
                # values are every row's), and its MLP mixes none
                keep = positions + n - len(tokens) if last else n
                o = fns["core"](*(a[at:at + n] for a in parts), max(0, n - keep))
                outs.append(o[-keep:])
                kept.append(x[at + n - keep:at + n])
                at += n
            del parts
            if last:
                lens = [positions + n - len(t) for n, t in zip(lens, tokens_list)]
            x = fns["output"](jnp.concatenate(kept), jnp.concatenate(outs), w).astype(dt)
            del outs, kept
            if mlp == "dense":
                x = fns["dense"](x, w)
            else:
                # the experts see the rows of every sequence at once (a token's MLP reads
                # no other token), so each held expert's matrices are fetched once a layer
                u = fns["read"](x, w)
                held = expert_weights(l)
                x = x + moe(u, w, ek, lambda e: tuple(cast(a[e]) for a in held), fns["add_expert"])
                del held, u
            x = jax.block_until_ready(x.astype(dt))
            del w
            print(f"kimik2 reference ({precision}): layer {l} ({mlp}) of {len(tokens_list)} sequences "
                  f"done {time.time() - t0:.1f} s in", flush=True)
        xs = list(jnp.split(x, np.cumsum(lens)[:-1]))
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
    del tp  # one device serves this share
    print(f"kimik2 reference ({precision}): starts; the launcher's greedy requests are done", flush=True)
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
# Bytes and operations of a decode step and of the two kernels


def latent_row(cfg: Dict[str, Any]) -> int:
    """Columns of a cached row AS THE POOLS ALLOCATE IT: the latent and
    the RoPE key, padded to whole lane tiles (the configuration's
    ``engine.kv_bytes_per_token`` over 2 B and the layers served): 640."""
    return int(cfg["engine"]["kv_bytes_per_token"]) // (2 * len(cfg["layers_served"]))


def latent_page_bytes(cfg: Dict[str, Any]) -> int:
    """Bytes of one page of ONE layer's pool as allocated (163,840)."""
    return int(cfg["engine"]["page_size"]) * 2 * latent_row(cfg)


def _sizes(cfg: Dict[str, Any]) -> Dict[str, float]:
    D, H = cfg["hidden_size"], cfg["num_attention_heads"]
    ql, R, dn, dr, Dv = (cfg["q_lora_rank"], cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
                         cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    kinds = layer_kinds(cfg)
    return {
        "D": D, "H": H, "R": R, "dr": dr, "n": len(kinds),
        # bfloat16 elements of one layer's attention
        "mla": D * (ql + R + dr) + ql * H * (dn + dr) + H * dn * R + H * R * Dv + H * Dv * D,
        "mla_f32": ql + R, "norms_f32": 2 * D,
        "dense": 3 * D * cfg["intermediate_size"],
        "shared": 3 * D * cfg["moe_intermediate_size"],
        "router_f32": D * cfg["n_routed_experts"] + cfg["n_routed_experts"],
        "expert": 3 * D * cfg["moe_intermediate_size"],
        "n_dense": sum(1 for f in kinds if f == "dense"), "n_sparse": sum(1 for f in kinds if f == "sparse"),
    }


def expert_bytes(cfg: Dict[str, Any]) -> int:
    """bfloat16 bytes of ONE routed expert's three matrices (88,080,384 at the published widths)."""
    return int(2 * _sizes(cfg)["expert"])


def fixed_weight_bytes(cfg: Dict[str, Any]) -> float:
    """Weights a decode step reads whatever it routes: everything outside
    the routed experts, and the head over the held vocabulary."""
    s = _sizes(cfg)
    bf16 = s["n"] * s["mla"] + s["n_dense"] * s["dense"] + s["n_sparse"] * s["shared"]
    f32 = s["n"] * (s["mla_f32"] + s["norms_f32"]) + s["D"] + s["n_sparse"] * s["router_f32"]
    return 2.0 * (bf16 + s["D"] * cfg["vocab_size"]) + 4.0 * f32


def expected_experts_hit(cfg: Dict[str, Any], rows: float) -> float:
    """Held experts a step of ``rows`` tokens reaches, summed over the
    expert layers, under a uniform router: ``held (1 - (1 - 1/E)^(k rows))``."""
    p = 1.0 - (1.0 - 1.0 / cfg["n_routed_experts"]) ** (cfg["num_experts_per_tok"] * max(rows, 0.0))
    return _sizes(cfg)["n_sparse"] * cfg["n_routed_experts_held"] * p


def decode_step_bytes(cfg: Dict[str, Any], rows: float, mean_context: float,
                      experts_hit: Optional[float] = None) -> float:
    """HBM bytes one decode step of ``rows`` sequences must move: the
    fixed weights once; the matrices of the experts HIT (summed over the
    expert layers: measured where the spans give it, else the uniform
    router's expectation); per row every cached row of its context as
    the pools hold it, in every layer, the new rows and an embedding row."""
    s = _sizes(cfg)
    hit = expected_experts_hit(cfg, rows) if experts_hit is None else experts_hit
    per_row = s["n"] * (mean_context + 1) * 2 * latent_row(cfg) + 2 * s["D"]
    return fixed_weight_bytes(cfg) + hit * expert_bytes(cfg) + rows * per_row


def decode_step_flops(cfg: Dict[str, Any], rows: float, mean_context: float) -> float:
    """Multiply-adds x 2 a step: every fixed matrix once a row, the held
    share of a row's 8 experts, the absorbed latent attention over the
    whole context in every layer (scores against the 576 columns that
    carry a key, values against 512, 64 heads)."""
    s = _sizes(cfg)
    held = cfg["num_experts_per_tok"] * cfg["n_routed_experts_held"] / cfg["n_routed_experts"]
    fixed = (s["n"] * s["mla"] + s["n_dense"] * s["dense"]
             + s["n_sparse"] * (s["shared"] + s["router_f32"] + held * s["expert"]) + s["D"] * cfg["vocab_size"])
    attn = s["n"] * s["H"] * (2 * s["R"] + s["dr"]) * mean_context
    return 2.0 * rows * (fixed + attn)


def decode_step_floor_s(cfg: Dict[str, Any], peaks: Dict[str, float], rows: float, mean_context: float,
                        experts_hit: Optional[float] = None) -> float:
    t_bytes = decode_step_bytes(cfg, rows, mean_context, experts_hit) / peaks["hbm_bytes_per_s"]
    t_flops = decode_step_flops(cfg, rows, mean_context) / peaks["bf16_flops_per_s"]
    return max(t_bytes, t_flops)


def latent_read_bytes_and_flops(cfg: Dict[str, Any], pages: float, tokens: float):
    """What the decode-side latent read needs for ``pages`` pool pages
    walked and ``tokens`` cached tokens read, ONE layer: the pages' bytes
    as the pool allocates them, and 64 heads x tokens x (key width 576 +
    value width 512) x 2 operations."""
    s = _sizes(cfg)
    return pages * latent_page_bytes(cfg), 2.0 * s["H"] * tokens * (2 * s["R"] + s["dr"])


# --------------------------------------------------------------------------- #
# Readers of this architecture's own spans and counters


_decode_steps_traced = _mla._decode_steps_traced  # decode programs the trace counted x ``decode_block``


def _kernel_self_s(ctx, params) -> Optional[float]:
    from perfbench import trace_reduce

    tr = ctx["trace"]
    if not tr or not tr.get("devices"):
        return None
    return trace_reduce.matching_s(tr["ops_self_s"], params["match"]) or None


def _latent_layers(ctx) -> Optional[float]:
    """The latent pools a decode step reads, as the spans say (``latent_layers``)."""
    from perfbench import readers

    return readers.span_mean(ctx, {"kind": "decode", "field": "latent_layers"})


def decode_roofline_share(ctx, params) -> Optional[float]:
    """``decode_step_floor_s`` with the experts HIT and the latent tokens
    READ a step that the decode spans report, over the measured device
    time of a step, percent."""
    from perfbench import readers

    step_ms = ctx["read"](params["time_metric"])
    rows = readers.span_mean(ctx, {"kind": "decode", "field": "rows"})
    read = _shared._span_ratio(ctx, "decode", "latent_tokens_read", "rows")  # one layer's, a row
    hit = readers.span_mean(ctx, {"kind": "decode", "field": "moe_experts_hit"})
    if not step_ms or not rows or read is None or hit is None or not _latent_layers(ctx):
        return None
    floor_s = decode_step_floor_s(ctx["config"], ctx["peaks"], rows, max(read - 1.0, 0.0), hit)
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
    if not self_s or hit_step is None or not _latent_layers(ctx):
        return None
    hit_chunk = readers.span_mean(ctx, {"kind": "prefill_chunk", "field": "moe_experts_hit"}) or 0.0
    hits = _decode_steps_traced(ctx) * hit_step + _shared._programs_traced(ctx["trace"], r"^jit_extend") * hit_chunk
    return 100.0 * hits * expert_bytes(ctx["config"]) / ctx["peaks"]["hbm_bytes_per_s"] / self_s


def latent_attention_roofline_share(ctx, params) -> Optional[float]:
    """The least time the chip could take for the latent pages the decode
    kernel walked in the traced interval, in every latent layer (the
    LARGER of their bytes, as the pools allocate a row, over the HBM peak
    and of the read's operations over the bf16 peak), over the kernel's
    self time there, percent. Pages and tokens: the decode steps the
    trace counted times what ONE layer's read of a step walked
    (``kv_pages_walked``) and read (``latent_tokens_read``) in the
    window's spans, times the layers the spans name (``latent_layers``)."""
    from perfbench import readers

    self_s = _kernel_self_s(ctx, params)
    pages = readers.span_mean(ctx, {"kind": "decode", "field": "kv_pages_walked"})
    tokens = readers.span_mean(ctx, {"kind": "decode", "field": "latent_tokens_read"})
    layers = _latent_layers(ctx)
    if not self_s or pages is None or tokens is None or not layers:
        return None
    steps = _decode_steps_traced(ctx)
    nbytes, flops = latent_read_bytes_and_flops(ctx["config"], steps * pages * layers, steps * tokens * layers)
    floor_s = max(nbytes / ctx["peaks"]["hbm_bytes_per_s"], flops / ctx["peaks"]["bf16_flops_per_s"])
    return 100.0 * floor_s / self_s


def prefix_reused_token_share(ctx, params) -> Optional[float]:
    """Prompt tokens served from the prefix store's shared pages over
    prompt tokens submitted, in the window, percent: the store's
    reused-token counter over itself plus the tokens the extend programs
    computed. A parent that cannot run the cell, or a store that is off
    (nothing reused), gives nothing to read."""
    from perfbench import readers

    del params
    grew = lambda name: (readers.metric_sum(ctx["metrics_after"], name)  # noqa: E731
                         - readers.metric_sum(ctx["metrics_before"], name))
    reused = grew("genai_engine_prefix_cache_tokens_reused_total")
    computed = grew("genai_engine_prefill_tokens_total")
    if reused <= 0 or not any(n == "genai_engine_prefix_shared_pages_in_use" for n, _ in ctx["metrics_after"]):
        return None
    return 100.0 * reused / (reused + computed)


# --------------------------------------------------------------------------- #
# Once, outside the per-run comparison (``python3 -m perfbench.arch.kimik2``): the
# two readings the tolerance is set from, and a prompt that enters through a store
# entry at the depth the traffic reaches (ISSUE 49 section 7 (d))


def _build_engine(cfg: Dict[str, Any]):
    """The configuration's engine with no server around it (the settings of ``cfg["engine"]``)."""
    from generativeaiexamples_tpu.config import EngineConfig
    from generativeaiexamples_tpu.engine.llm_engine import LLMEngine
    from generativeaiexamples_tpu.utils import jax_env

    from perfbench.tokenizer_file import write_tokenizer

    jax_env.bootstrap()
    register(cfg)
    e = cfg["engine"]
    # the harness's character tokenizer over the held vocabulary, so that the
    # seeded prompts are the harness's own (perfbench/run.py ``server_env``)
    tok = os.path.join(tempfile.mkdtemp(prefix="kimik2-"), "tokenizer.json")
    write_tokenizer(tok, cfg["vocab_size"])
    return LLMEngine(EngineConfig(
        model_config_name=cfg["name"], tokenizer_path=tok, max_batch_size=e["max_batch_size"], max_seq_len=e["max_seq_len"],
        prefill_chunk=e["prefill_chunk"], prefill_wave_tokens=e["prefill_wave_tokens"], page_size=e["page_size"],
        kv_pool_pages=e["kv_pool_pages"], decode_block=e["decode_block"], prefix_cache_enable="auto",
        prefix_cache_slots=e["prefix_cache_slots"], warmup_prompt_lengths="",
    ))


def tolerance_readings(eng, cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The two readings ``TOLERANCE`` is set from, on this machine's own
    draws of the weights: the SERVED walks' last-position logits and the
    all-bfloat16 control's, each against the float32 reference, prompt
    by prompt (``reference.prompt_tokens``)."""
    from generativeaiexamples_tpu.utils import jax_env

    prompts = _harness_prompts(eng, cfg, cfg["reference"]["prompt_tokens"], HARNESS_PROMPT_SEED)
    served = _served_logits(eng, prompts)
    one = dict(cfg, reference=dict(cfg["reference"], decode_tokens=0))
    t0 = time.time()
    ref = reference_logits(eng, one, prompts, device=jax_env.host_device())
    t1 = time.time()
    low = reference_logits(eng, one, prompts, device=jax_env.host_device(), precision="bfloat16")
    err = lambda a, b: float(np.max(np.abs(np.asarray(a, np.float32) - b)) / max(float(np.max(np.abs(b))), 1e-6))  # noqa: E731
    return {"prompt_tokens": [len(p) for p in prompts],
            "served_prefill_rel_err": [err(s, r[len(p) - 1]) for s, r, p in zip(served, ref, prompts)],
            "control_prefill_rel_err": [err(c[len(p) - 1], r[len(p) - 1]) for c, r, p in zip(low, ref, prompts)],
            "reference_host_s": round(t1 - t0, 1), "control_host_s": round(time.time() - t1, 1)}


def deep_prefix_check(eng, cfg: Dict[str, Any], prompt_tokens: int = 16640, decode_tokens: int = 8) -> Dict[str, Any]:
    """Serve a seeded prompt of ``prompt_tokens`` for one token (the
    store keeps an entry at the chunk-aligned depth below it), serve it
    again for ``decode_tokens`` greedy tokens (the admission maps the
    entry's pages and prefills the tail), and hold those tokens to the
    plain reference over the whole sequence. Returns the readings."""
    from generativeaiexamples_tpu.engine.llm_engine import SamplingParams
    from generativeaiexamples_tpu.utils import jax_env

    prompt = _harness_prompts(eng, cfg, [prompt_tokens], HARNESS_PROMPT_SEED + 1)[0]
    t0 = time.time()
    depth = prime_prefix_store(eng, [prompt])
    hits0 = eng.metrics.get("prefix_cache_hits", 0)
    tokens = list(eng.iter_ids(prompt, SamplingParams(temperature=0.0, max_tokens=decode_tokens), timeout=900))
    served_s = time.time() - t0
    hit = eng.metrics.get("prefix_cache_hits", 0) - hits0
    t1 = time.time()
    ref = reference_logits(eng, dict(cfg, reference=dict(cfg["reference"], decode_tokens=decode_tokens)),
                           [prompt + tokens], device=jax_env.host_device())[0]
    margins, ranks = [], []
    for j, tok in enumerate(tokens):
        row = ref[len(prompt) - 1 + j]
        margins.append(float((np.max(row) - row[tok]) / max(float(np.max(np.abs(row))), 1e-6)))
        ranks.append(int(np.sum(row > row[tok])))  # 0: the reference's own argmax
    return {"prompt_tokens": prompt_tokens, "entry_depth": depth, "prefix_hits": hit, "tokens": tokens,
            "decode_margins": margins, "reference_ranks": ranks, "decode_margin_max": max(margins) if margins else None, "tolerance": TOLERANCE,
            "ok": bool(margins) and len(tokens) == decode_tokens and hit >= 1 and max(margins) <= TOLERANCE,
            "served_s": round(served_s, 1), "reference_host_s": round(time.time() - t1, 1)}


if __name__ == "__main__":
    import argparse
    import json

    ap = argparse.ArgumentParser(description="once, outside the per-run comparison: the tolerance's two readings and "
                                             "the deep prefix comparison of ISSUE 49 section 7 (d)")
    ap.add_argument("--config", default=os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                                                     "configs", "kimi-k2.5-ep32-bf16.json"))
    ap.add_argument("--control", action="store_true", help="the served walks' and the all-bfloat16 control's readings")
    ap.add_argument("--prompt-tokens", type=int, default=0, help="the deep prefix comparison at this many tokens")
    args = ap.parse_args()
    with open(args.config, encoding="utf-8") as fh:
        config = json.load(fh)
    engine = _build_engine(config)
    try:
        if args.control:
            print("tolerance_readings: " + json.dumps(tolerance_readings(engine, config)), flush=True)
        if args.prompt_tokens:
            print("deep_prefix_check: " + json.dumps(deep_prefix_check(engine, config, args.prompt_tokens)), flush=True)
    finally:
        engine.shutdown()
