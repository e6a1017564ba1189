"""Resident draft-model runtime for speculative decoding.

``spec_proposer='draft_model'`` (or ``'combined'``) builds a SECOND,
small Llama next to the serving target — own weights, own private
per-slot KV cache (per-layer strips, not pages of the target's pool),
sharded on the same mesh — and drafts K tokens for the whole decode wave in ONE batched compiled dispatch per spec round
(models/llama.py ``draft_propose_layers``: a catch-up chunk feeding the
tokens the target emitted since each row's draft frontier, fused with a
``lax.scan`` of K-1 greedy draft steps). The engine then issues its
existing single spec-verify dispatch, so the per-emitted-token cost is
``draft_cost + verify_cost / (accepted + 1)`` — a win whenever the
draft is meaningfully smaller than the target and acceptance is
moderate (RTP-LLM's production spec serving and the survey's
draft-model section, PAPERS.md).

Design notes:

- the draft KV cache is dense per-slot strips, one set per layer
  (``llama.init_kv_cache_layers``), not pages: at draft scale the
  strips are a rounding error next to the target pool, and they keep
  the draft programs off the page-table plumbing entirely;
- all host bookkeeping (the per-slot draft frontier and its
  acceptance-rewind arithmetic) lives in
  ``spec_decode.DraftTracker`` — pure host, tier-1-testable;
- every compiled draft program is registered with the engine's
  compile watch (``draft_prefill`` / ``draft_propose`` families) and
  pre-compiled by :meth:`DraftRuntime.warmup`, which
  ``LLMEngine.warmup_spec_shapes`` runs inside its warmup scope — the
  loadgen hot-path-compile gate stays at zero with the draft resident;
- the runtime is single-writer: every method runs on the engine's
  dispatch thread (admission prefill, per-round proposal, release), so
  no lock guards its state.
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from generativeaiexamples_tpu.engine import spec_decode as spec_decode_mod
from generativeaiexamples_tpu.utils import get_logger
from generativeaiexamples_tpu.utils import jax_env

logger = get_logger(__name__)


def resolve_draft_config(cfg):
    """The draft model's LlamaConfig: ``spec_draft_checkpoint_path``'s
    own config.json when present, else the ``spec_draft_model`` preset.
    Raises ValueError naming the knob on an unknown preset."""
    from generativeaiexamples_tpu.models import llama

    if getattr(cfg, "spec_draft_checkpoint_path", ""):
        from generativeaiexamples_tpu.models.hf_loader import config_from_hf

        model_cfg = config_from_hf(cfg.spec_draft_checkpoint_path)
        if model_cfg is not None:
            return model_cfg
    name = getattr(cfg, "spec_draft_model", "")
    if name not in llama.PRESETS:
        raise ValueError(
            f"spec_draft_model must name a models/llama.py preset "
            f"({', '.join(sorted(llama.PRESETS))}), got {name!r}"
        )
    return llama.PRESETS[name]


class DraftRuntime:
    """Device half of the resident-draft proposer.

    Built by the engine (eagerly at init when ``spec_proposer`` asks
    for a draft model, lazily by ``set_spec_proposer`` for bench A/Bs).
    Holds the draft weights + caches + two compiled programs:

    - ``draft_prefill``: ``extend_layers`` chunk dispatches writing an
      admitted wave's prompts into the draft cache (fixed shapes:
      ladder row rungs x chunk windows — the same bounded-executable
      discipline as the target's chunked prefill);
    - ``draft_propose``: the fused catch-up + K-step greedy draft
      (models/llama.py ``draft_propose_layers``), one executable per
      attention-window rung.
    """

    def __init__(
        self,
        cfg,
        *,
        mesh,
        compile_watch,
        dtype,
        sample_vocab: int,
        shapes,
    ) -> None:
        import jax
        import jax.numpy as jnp

        from generativeaiexamples_tpu.models import llama
        from generativeaiexamples_tpu.parallel.mesh import mesh_context

        self._jnp = jnp
        self._llama = llama
        self._mesh = mesh
        # the target's ladders (engine/scheduler/shapes.py ShapePlan):
        # draft warm-up compiles exactly the shapes admission produces
        self._shapes = shapes
        self.num_slots = num_slots = shapes.num_slots
        self.max_seq_len = max_seq_len = shapes.max_seq_len
        dcfg = self.draft_config = resolve_draft_config(cfg)
        if dcfg.max_seq_len < max_seq_len:
            raise ValueError(
                f"spec_draft_model window ({dcfg.max_seq_len}) is "
                f"shorter than the serving capacity ({max_seq_len}); "
                f"the draft cache mirrors the target's positions, so "
                f"pick a draft config with max_seq_len >= the engine's"
            )
        # Proposals must be ids the target can emit; a smaller draft
        # head only lowers acceptance, a vocab below the target's
        # sampling slice would make the argmax unrepresentative.
        self._vocab = min(sample_vocab, dcfg.vocab_size)
        if dcfg.vocab_size < sample_vocab:
            logger.warning(
                "spec draft model vocab (%d) is smaller than the "
                "target's sampling vocab (%d); drafts are clamped to "
                "the shared prefix — expect lower acceptance.",
                dcfg.vocab_size, sample_vocab,
            )
        self._k = spec_decode_mod.effective_draft_len(cfg)
        self._c0 = self._k + 1  # catch-up width (DraftTracker invariant)
        self.tracker = spec_decode_mod.DraftTracker(self._k)
        C = self._chunk = min(cfg.prefill_chunk, max_seq_len)
        cap = shapes.max_wave_rows()
        self._row_rungs = sorted({min(s, cap) for s in shapes.wave_sizes()})
        self._chunk_windows = sorted({
            shapes.attention_window(min((k + 1) * C, max_seq_len))
            for k in range((max_seq_len + C - 1) // C)
        })
        self._window_rungs = shapes.window_rungs()
        self._kv_quant = (
            getattr(cfg, "spec_draft_kv_dtype", "bfloat16") == "int8"
        )

        # --- draft weights (dense — a small model never needs packing)
        params = None
        ckpt = getattr(cfg, "spec_draft_checkpoint_path", "")
        with jax.default_device(jax_env.host_device()):
            if ckpt:
                from generativeaiexamples_tpu.models.hf_loader import load_params

                params = load_params(ckpt, dcfg, dtype)
                logger.info("Loaded draft-model weights from %s", ckpt)
            else:
                params = llama.init_params_fast(dcfg, 0, dtype)
                logger.warning(
                    "Resident draft model running with random-init "
                    "weights (no spec_draft_checkpoint_path)."
                )
        caches = llama.init_kv_cache_layers(
            dcfg, num_slots, max_seq_len, dtype, quantized=self._kv_quant
        )
        if mesh.size > 1:
            from generativeaiexamples_tpu.parallel.sharding import (
                shard_draft_kv_cache,
                shard_params,
                shard_params_layered,
            )

            with mesh_context(mesh):
                params = shard_params(params, mesh)
                self._params = shard_params_layered(
                    llama.consume_split_params_layers(params), mesh
                )
                self._caches = shard_draft_kv_cache(
                    caches, mesh, quantized=self._kv_quant
                )
        else:
            device = mesh.devices.reshape(-1)[0]
            params = jax.device_put(params, device)
            self._params = llama.consume_split_params_layers(params)
            self._caches = jax.device_put(caches, device)
        del params, caches

        # --- compiled programs (registered with the compile watch so
        # the hot-path gate covers the draft families too)
        K, V = self._k, self._vocab

        def draft_prefill(params, caches, tokens, offsets, valid, slots,
                          window):
            _, caches = llama.extend_layers(
                params, dcfg, tokens, offsets, valid, slots, caches,
                window, quant_kernel=False,
            )
            return caches

        def draft_propose(params, caches, tokens, offsets, valid, window):
            return llama.draft_propose_layers(
                params, dcfg, tokens, offsets, valid, caches, window,
                draft_k=K, vocab=V, quant_kernel=False,
            )

        wrap = compile_watch.wrap
        self._write_prompts_fn = wrap(
            "draft_prefill",
            jax.jit(draft_prefill, donate_argnums=(1,), static_argnums=(6,)),
        )
        self._propose_fn = wrap(
            "draft_propose",
            jax.jit(draft_propose, donate_argnums=(1,), static_argnums=(5,)),
        )
        logger.info(
            "resident draft model: %d layers x %d hidden (target %d "
            "slots, K=%d, kv=%s)",
            dcfg.num_layers, dcfg.hidden_size, num_slots, K,
            "int8" if self._kv_quant else "bf16",
        )

    # ------------------------------------------------------------------ #
    # slot lifecycle (dispatch thread)
    def on_admit(self, slot: int, prompt_len: int) -> None:
        self.tracker.on_admit(slot, prompt_len)

    def on_release(self, slot: int) -> None:
        self.tracker.on_release(slot)

    def reset(self) -> None:
        self.tracker.reset()

    # ------------------------------------------------------------------ #
    def prefill_wave(
        self,
        tokens: np.ndarray,  # [Np, bucket] the admission wave's prompts
        lengths: np.ndarray,  # [Np]
        slots: np.ndarray,  # [Np]
        eligible: np.ndarray,  # [Np] bool — rows that will draft
    ) -> None:
        """Write the admitted wave's prompts into the draft KV cache:
        groups of ladder-padded rows x fixed-shape chunk dispatches (the
        same bounded executable set warmup compiles). The draft has no
        prefix cache — warm target rows still feed their FULL prompt
        here (correctness-simple; the draft pass is cheap by
        construction). Frontier bookkeeping (``tracker.on_admit``) is
        the CALLER's job, after its proposer context is seeded."""
        jnp = self._jnp
        rows = [i for i in range(len(slots)) if eligible[i]]
        if not rows:
            return
        C = self._chunk
        cap = self._row_rungs[-1]
        for g0 in range(0, len(rows), cap):
            grp = rows[g0:g0 + cap]
            n = min(self._shapes.wave_pad(len(grp)), cap)
            tmax = int(max(lengths[i] for i in grp))
            # Pad up the rung by repeating row 0 WHOLE (tokens, length,
            # slot) — the engine's padding contract: duplicate rows
            # scatter IDENTICAL values at identical indices, which is
            # well-defined. A zero-valid pad sharing a real slot would
            # instead race its read-back-and-rewrite against the real
            # row's fresh writes at the same scatter indices.
            tok = np.tile(tokens[grp[0]], (n, 1)).astype(np.int32)
            lens = np.full((n,), int(lengths[grp[0]]), np.int32)
            slot_rows = np.full((n,), int(slots[grp[0]]), np.int32)
            for j, i in enumerate(grp):
                tok[j] = tokens[i]
                lens[j] = lengths[i]
                slot_rows[j] = slots[i]
            for k in range((tmax + C - 1) // C):
                tok_k = np.zeros((n, C), np.int32)
                seg = tok[:, k * C:(k + 1) * C]
                tok_k[:, : seg.shape[1]] = seg
                valid = np.clip(lens - k * C, 0, C).astype(np.int32)
                offsets = np.full((n,), k * C, np.int32)
                W = self._shapes.attention_window(
                    min((k + 1) * C, self.max_seq_len)
                )
                self._caches = self._write_prompts_fn(
                    self._params,
                    self._caches,
                    jnp.asarray(tok_k),
                    jnp.asarray(offsets),
                    jnp.asarray(valid),
                    jnp.asarray(slot_rows),
                    W,
                )
                spec_decode_mod.record_draft_dispatch(program="prefill")

    def propose(
        self, rows: Sequence[Tuple[int, Sequence[int], int]]
    ) -> Dict[int, List[int]]:
        """One spec round's batched draft dispatch.

        ``rows``: ``[(slot, ctx, cap)]`` for every live eligible row.
        Every row with draft state gets its pending context fed
        (catch-up) whether or not its cap lets it draft this round —
        bounded pending spans are what keep the catch-up width static.
        Returns ``{slot: proposal}`` truncated to each row's cap; the
        sync on the proposal slab is the draft-model analogue of the
        lookup proposer's host scan (the verify draft needs host
        values)."""
        jnp = self._jnp
        B, C0 = self.num_slots, self._c0
        chunk = np.zeros((B, C0), np.int32)
        offsets = np.zeros((B,), np.int32)
        valid = np.zeros((B,), np.int32)
        spans: Dict[int, Tuple[int, int]] = {}  # slot -> (cap, ctx_len)
        for slot, ctx, cap in rows:
            span = self.tracker.begin_round(slot, len(ctx))
            if span is None:
                continue
            fed, pending = span
            chunk[slot, :pending] = ctx[fed:]
            offsets[slot] = fed
            valid[slot] = pending
            spans[slot] = (cap, len(ctx))
        if not spans:
            return {}
        needed = int(
            max(offsets[s] + valid[s] for s in spans) + self._k + 1
        )
        W = self._shapes.attention_window(min(needed, self.max_seq_len))
        t0 = time.time()
        out, self._caches = self._propose_fn(
            self._params,
            self._caches,
            jnp.asarray(chunk),
            jnp.asarray(offsets),
            jnp.asarray(valid),
            W,
        )
        # The proposal slab must reach the host before the verify draft
        # is assembled — the draft-model bargain, mirroring the spec
        # path's existing verify sync. (Visible to the lint since the
        # dispatch-readback rule went interprocedural: the dispatch loop
        # reaches this through DraftModelProposer.)
        # genai-lint: disable=dispatch-readback -- allow-listed draft sync: the proposal slab feeds the NEXT verify dispatch's host-assembled draft, so it must land before the loop continues
        out_np = np.asarray(out)
        spec_decode_mod.record_draft_dispatch()
        self.last_dispatch_s = time.time() - t0
        result: Dict[int, List[int]] = {}
        for slot, (cap, ctx_len) in spans.items():
            self.tracker.mark_fed(slot, ctx_len)
            k = max(0, min(cap, self._k))
            if k:
                result[slot] = [int(t) for t in out_np[slot, :k]]
        return result

    # ------------------------------------------------------------------ #
    def warmup(self) -> None:
        """Compile the full draft executable set with zero-valid (value
        no-op) dispatches: ``draft_prefill`` at every (row rung, chunk
        window), ``draft_propose`` at every window rung. Caller holds
        the engine's warmup scope + quiesced decode (the caches are
        donated)."""
        jnp = self._jnp
        C = self._chunk
        for n in self._row_rungs:
            tok = jnp.zeros((n, C), jnp.int32)
            off = jnp.zeros((n,), jnp.int32)
            valid = jnp.zeros((n,), jnp.int32)
            slot_rows = jnp.zeros((n,), jnp.int32)
            for W in self._chunk_windows:
                self._caches = self._write_prompts_fn(
                    self._params, self._caches, tok, off, valid,
                    slot_rows, W,
                )
        B, C0 = self.num_slots, self._c0
        tok = jnp.zeros((B, C0), jnp.int32)
        off = jnp.zeros((B,), jnp.int32)
        valid = jnp.zeros((B,), jnp.int32)
        last = None
        for W in self._window_rungs:
            last, self._caches = self._propose_fn(
                self._params, self._caches, tok, off, valid, W
            )
        if last is not None:
            last.block_until_ready()
