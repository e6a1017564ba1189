"""Speculative decoding: the host-side half and the proposer seam.

Two draft sources share ONE verify/acceptance contract (PAPERS.md:
RTP-LLM, arXiv:2605.29639; the serving survey arXiv:2407.12391
§speculative decoding):

- **prompt lookup** (draft-model-free): RAG and multi-turn outputs copy
  long spans verbatim from retrieved context and chat history, so the
  cheapest draft model is the request's OWN token buffer — match the
  tail of the generated sequence against the prompt+output tokens and
  propose the continuation of the most recent earlier occurrence;
- **resident draft model** (``spec_proposer='draft_model'``): a second,
  small Llama built alongside the target (engine/spec_draft.py) drafts
  K greedy tokens for the whole decode wave in one batched compiled
  dispatch — generalizing speculation to NORMAL (non-copy-heavy)
  chat/RAG traffic, where lookup rarely matches.

Either way the engine scores all K draft positions for a wave of slots
in ONE compiled verify dispatch (models/llama.py ``verify_layers``) and
accepts the longest matching prefix per row against the target's own
(greedy or seeded-sampled) outputs — proposals can never change a
stream, only how many tokens each dispatch emits.

This module is import-light (no jax): the :class:`SpecProposer` seam
(lookup / draft-model / combined), the draft-length capping rule every
proposer shares, the pure-host draft-frontier bookkeeping
(:class:`DraftTracker` — the acceptance-rewind math), a host mirror of
the device acceptance rule (tests), and the spec metric families. The
compiled verify step and the scheduler integration live in
engine/llm_engine.py; the draft-model device runtime in
engine/spec_draft.py; knobs are ``spec_decode_enable`` /
``spec_proposer`` / ``spec_draft_*`` / ``spec_ngram_max``
(docs/spec_decode.md).
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from generativeaiexamples_tpu.utils import metrics as metrics_mod

# --------------------------------------------------------------------------- #
# Metric families (process-global, registered at import — a scrape sees
# the full catalog without an engine ever being built, like the engine's
# own families in llm_engine.py).
_REG = metrics_mod.get_registry()
_M_DRAFTED = _REG.counter(
    "genai_engine_spec_drafted_tokens_total",
    "Draft tokens proposed by the prompt-lookup speculator.",
)
_M_ACCEPTED = _REG.counter(
    "genai_engine_spec_accepted_tokens_total",
    "Draft tokens accepted by the verify dispatch (greedy prefix match).",
)
_M_ACCEPTANCE = _REG.histogram(
    "genai_engine_spec_acceptance_ratio",
    "Per-(row, dispatch) fraction of drafted tokens accepted.",
    buckets=(0.0, 0.125, 0.25, 0.375, 0.5, 0.625, 0.75, 0.875, 1.0),
)
_M_DISPATCH_TOKENS = _REG.histogram(
    "genai_engine_spec_dispatch_tokens",
    "Tokens emitted per live row per verify dispatch (accepted + bonus).",
    buckets=(1, 2, 3, 4, 6, 8, 12, 16, 24, 32),
)
_M_DRAFT_DISPATCHES = _REG.counter(
    "genai_engine_spec_draft_dispatches_total",
    "Batched resident-draft-model dispatches by program: "
    "program='propose' (one fused catch-up + K-step draft launch per "
    "spec round, drafting for every live spec slot at once) and "
    "program='prefill' (admission-time chunk dispatches writing a "
    "wave's prompts into the draft KV cache) — engine/spec_draft.py. "
    "Together they are the draft model's FULL launch cost (the "
    "draft_dispatch_share loadgen/bench report). Zero under the "
    "prompt-lookup proposer, whose drafts are host-side n-gram scans.",
    ("program",),
)
_M_ADAPTIVE_ROUNDS = _REG.counter(
    "genai_engine_spec_adaptive_rounds_total",
    "Spec verify rounds dispatched with acceptance-adaptive draft "
    "width enabled (spec_adaptive_k=on). Together with "
    "genai_engine_spec_adaptive_k_picked_total this yields the mean effective "
    "verify width K per round (the loadgen spec block's "
    "effective_k_mean).",
)
_M_ADAPTIVE_K_SUM = _REG.counter(
    "genai_engine_spec_adaptive_k_picked_total",
    "Sum of the per-round effective draft widths K picked by the "
    "adaptive-K ladder (divide by "
    "genai_engine_spec_adaptive_rounds_total for the mean).",
)

# The proposer registry: values the ``spec_proposer`` knob accepts.
# 'lookup' is the exact PR 3 prompt-lookup path; 'draft_model' drafts
# with the resident small model; 'combined' tries lookup first and
# falls back to the draft model's proposal where the n-gram scan finds
# nothing (copy-heavy spans still draft for free; everything else gets
# the model).
PROPOSER_KINDS = ("lookup", "draft_model", "combined")


def effective_draft_len(cfg) -> int:
    """THE draft width K every layer agrees on — the verify program's
    chunk width, ``cap_draft_len`` callers, the paged admission
    funding slack (``decode_block + K + 1``), and the draft-model
    program's step count all read this one rule, so the draft path can
    never propose past the funded page reservation.

    ``spec_draft_model_len`` (> 0, draft-model/combined proposers only)
    overrides ``spec_draft_len``; 0 inherits it."""
    k = max(1, cfg.spec_draft_len)
    if getattr(cfg, "spec_proposer", "lookup") in ("draft_model", "combined"):
        override = getattr(cfg, "spec_draft_model_len", 0)
        if override > 0:
            k = override
    return k


def adaptive_k_ladder(k_max: int, k_min: int) -> Tuple[int, ...]:
    """The CLOSED set of verify widths adaptive K may pick, descending:
    halvings from ``k_max`` down to ``k_min`` inclusive (8 -> [8, 4, 2,
    1]). A closed ladder — not arbitrary integers — is what keeps the
    verify executable set warmable: warmup_spec_shapes walks exactly
    these rungs, so no acceptance trajectory can reach an uncompiled
    shape (the hot-path-compile gate stays zero)."""
    k_max = max(1, int(k_max))
    k_min = max(1, min(int(k_min), k_max))
    rungs: List[int] = []
    k = k_max
    while k > k_min:
        rungs.append(k)
        k = max(k_min, k // 2)
    rungs.append(k_min)
    return tuple(rungs)


class AdaptiveK:
    """Acceptance-adaptive verify width (``spec_adaptive_k=on``).

    Fixed-K speculation burns K+1-wide verify dispatches even when the
    workload stops accepting drafts (RTP-LLM, PAPERS.md, tunes
    speculation to measured acceptance in production for exactly this
    reason). This policy picks each round's draft width from the
    rolling AcceptanceTracker window (engine/scheduler/base.py):

    - no evidence yet (``ratio() is None``) -> ``k_max`` (optimism —
      the window needs data before shrinking);
    - ratio >= ``threshold`` -> ``k_max``. This is the IDENTITY
      guarantee the tests pin: a load whose acceptance never dips below
      the threshold runs every round at k_max, bit-identical to
      fixed-K;
    - otherwise the smallest ladder rung covering the EXPECTED
      acceptance depth ``ceil(ratio * k_max)`` (floored at ``k_min``) —
      collapsed acceptance pays narrow dispatches instead of wide ones;
    - every ``probe_interval``-th consecutive shrunk round runs
      ``k_max`` anyway, so a recovered workload re-measures at full
      width instead of being stuck narrow (the same probe discipline
      as AcceptanceTracker.should_draft).

    Funding is NOT adaptive: the one-K rule (:func:`effective_draft_len`)
    still bounds the paged admission slack at the configured max, so a
    probe round can never propose past a funded reservation.

    Single-writer (engine dispatch thread), pure host arithmetic.
    """

    def __init__(
        self,
        k_max: int,
        k_min: int = 1,
        threshold: float = 0.5,
        probe_interval: int = 16,
    ) -> None:
        self.k_max = max(1, int(k_max))
        self.k_min = max(1, min(int(k_min), self.k_max))
        self.threshold = float(threshold)
        self.probe_interval = max(1, int(probe_interval))
        self.ladder = adaptive_k_ladder(self.k_max, self.k_min)
        self._shrunk_rounds = 0

    def pick(self, ratio: Optional[float]) -> int:
        """Draft width for the next spec round given the tracker's
        rolling acceptance ratio (None = insufficient evidence)."""
        if ratio is None or ratio >= self.threshold:
            self._shrunk_rounds = 0
            return self.k_max
        self._shrunk_rounds += 1
        if self._shrunk_rounds >= self.probe_interval:
            # Probe round: full width once, so the window keeps seeing
            # deep-acceptance evidence and can recover.
            self._shrunk_rounds = 0
            return self.k_max
        want = max(self.k_min, min(self.k_max, int(np.ceil(ratio * self.k_max))))
        for k in reversed(self.ladder):  # ascending rungs
            if k >= want:
                return k
        return self.k_max


def require_verify_walk(model: str, cfg, fixed_state: bool) -> None:
    """Speculative decoding needs the family's verify walk
    (``verify_paged``, models/registry.py); a family that registers none
    is refused it at engine build. For a fixed-state model the reason is
    deeper than a missing walk: verify advances a row by up to K+1
    positions and rolls the rejected ones back by position alone — sound
    when a slot's state is pages, not when it is a recurrent state that
    the rejected tokens have already gone through."""
    if cfg.spec_decode_enable != "on":
        return
    if fixed_state:
        raise ValueError(
            f"{model} keeps a fixed per-slot state beside the page pool, "
            "which speculative verify cannot carry (a rejected draft "
            "token cannot be taken back out of a recurrent state); set "
            "spec_decode_enable='off'"
        )
    raise ValueError(
        f"{model} cannot be served with speculative decoding: its family "
        "registers no verify walk (verify_paged is None, "
        "models/registry.py); set spec_decode_enable='off'"
    )


def validate_config(cfg) -> None:
    """Engine-config validation for the spec-decode knobs (pure host, so
    tier-1 tests cover it without building an engine). Raises ValueError
    with the same phrasing as the engine's other knob checks."""
    if cfg.spec_decode_enable not in ("on", "off"):
        raise ValueError(
            f"spec_decode_enable must be on|off, got "
            f"{cfg.spec_decode_enable!r}"
        )
    if cfg.spec_draft_len < 1:
        raise ValueError(
            f"spec_draft_len must be >= 1, got {cfg.spec_draft_len}"
        )
    if cfg.spec_ngram_max < 1:
        raise ValueError(
            f"spec_ngram_max must be >= 1, got {cfg.spec_ngram_max}"
        )
    proposer = getattr(cfg, "spec_proposer", "lookup")
    if proposer not in PROPOSER_KINDS:
        raise ValueError(
            f"spec_proposer must be one of {'|'.join(PROPOSER_KINDS)}, "
            f"got {proposer!r}"
        )
    if getattr(cfg, "spec_draft_model_len", 0) < 0:
        raise ValueError(
            f"spec_draft_model_len must be >= 0 (0 = inherit "
            f"spec_draft_len), got {cfg.spec_draft_model_len}"
        )
    if getattr(cfg, "spec_draft_kv_dtype", "bfloat16") not in (
        "bfloat16", "int8"
    ):
        raise ValueError(
            f"spec_draft_kv_dtype must be 'bfloat16' or 'int8', got "
            f"{cfg.spec_draft_kv_dtype!r}"
        )
    adaptive = getattr(cfg, "spec_adaptive_k", "off")
    if adaptive not in ("on", "off"):
        raise ValueError(
            f"spec_adaptive_k must be on|off, got {adaptive!r}"
        )
    k_min = getattr(cfg, "spec_adaptive_k_min", 1)
    if not 1 <= k_min <= effective_draft_len(cfg):
        raise ValueError(
            f"spec_adaptive_k_min must be in [1, {effective_draft_len(cfg)}] "
            f"(the effective draft width), got {k_min}"
        )
    thr = getattr(cfg, "spec_adaptive_k_threshold", 0.5)
    if not 0.0 < thr <= 1.0:
        raise ValueError(
            f"spec_adaptive_k_threshold must be in (0, 1], got {thr}"
        )
    if proposer in ("draft_model", "combined"):
        if not (
            getattr(cfg, "spec_draft_model", "")
            or getattr(cfg, "spec_draft_checkpoint_path", "")
        ):
            raise ValueError(
                f"spec_proposer={proposer!r} needs a resident draft "
                f"model: set spec_draft_model (a models/llama.py preset "
                f"name) or spec_draft_checkpoint_path"
            )


def propose(ctx: Sequence[int], max_ngram: int, draft_len: int) -> List[int]:
    """Prompt-lookup draft for one row: match the longest tail n-gram
    (n = max_ngram down to 1) against an earlier occurrence in ``ctx``
    (the request's prompt + generated tokens) and return up to
    ``draft_len`` tokens following the MOST RECENT match.

    Longest n first (precision), and within an n the NEWEST match with a
    FULL ``draft_len`` continuation — generated text locally continues
    its latest pattern (a copied span, a repetition loop), but the very
    newest match of a loop sits near the buffer end and truncates its
    continuation, so full-width matches win over newer-but-shorter ones
    (the continuation may overlap the tail itself; that is what lets a
    period-p loop draft whole K-token blocks). The newest short
    continuation is the fallback when no full one exists. Returns []
    when nothing matches (the engine then runs the row as a plain
    single-token step inside the same verify dispatch).

    The n-gram scan is a vectorized numpy sliding-window compare (C
    speed, ~10 µs at an 8k-token buffer against a ~10 ms dispatch); the
    Python fallback loop over match starts runs at most ``draft_len``
    iterations before a full-width continuation is found (dense
    repetition) and rarely more than a handful otherwise. Called by the
    dispatch thread OUTSIDE the engine lock — the per-slot buffers are
    single-writer (dispatch-thread-owned), so proposals never block
    submit() or the reader's emissions.
    """
    n_ctx = len(ctx)
    if draft_len <= 0 or n_ctx < 2:
        return []
    arr = np.asarray(ctx, dtype=np.int64)
    for n in range(min(max_ngram, n_ctx - 1), 0, -1):
        tail = arr[n_ctx - n:]
        # match starts 0 .. n_ctx-1-n: the match must END before the
        # tail starts so at least one continuation token exists
        windows = np.lib.stride_tricks.sliding_window_view(arr[:-1], n)
        hits = np.nonzero((windows == tail).all(axis=1))[0]
        if hits.size == 0:
            continue
        short_cont: List[int] = []
        for start in hits[::-1]:  # newest-first
            cont = arr[start + n:start + n + draft_len]
            if cont.size == draft_len:
                return [int(t) for t in cont]
            if cont.size and not short_cont:
                short_cont = [int(t) for t in cont]
        if short_cont:
            return short_cont
    return []


def draft_eligible(params) -> bool:
    """Whether a request's sampling params allow prompt-lookup drafting:
    greedy (temperature <= 0) and not opted out (``spec_decode`` is not
    False). THE lookup eligibility rule — admission buffer-seeding, the
    engine's draftable-batch gate, and per-dispatch proposal all go
    through :meth:`SpecProposer.eligible` (which the lookup proposer
    routes here) so they cannot drift."""
    return params.temperature <= 0 and params.spec_decode is not False


# --------------------------------------------------------------------------- #
# The proposer seam: prompt-lookup, resident-draft-model, and combined
# proposers behind one interface. The engine owns clamping (every
# proposer receives caps from the SAME cap_draft_len rule) and the
# token-identical acceptance contract (the verify program never cares
# where a draft came from); a proposer only decides WHAT to propose.


class SpecProposer:
    """One draft source for the spec-decode subsystem.

    All hooks run on the engine's dispatch thread (single writer — the
    same ownership discipline as the per-slot ``_spec_ctx`` buffers):

    - ``eligible(params)``: whether a request's sampling params allow
      this proposer to draft for it. Lookup keeps PR 3's greedy-only
      rule; the draft-model proposers also draft sampled rows — the
      verify program samples every position with the same pure
      (seed, position) keys plain decode uses, so acceptance against
      sampled outputs is exactly as stream-preserving as greedy.
    - ``on_admit(slot, prompt_len)``: a draft-capable request claimed
      ``slot`` and its proposer context was seeded (prompt + first
      token). The draft-model proposer records the slot's draft-KV
      frontier here (its prompt was just prefilled into the draft
      cache).
    - ``on_release(slot)``: the slot left the decode batch.
    - ``propose_wave(rows)``: one spec round. ``rows`` is
      ``[(slot, ctx, cap)]`` for every live eligible row — ``ctx`` the
      slot's prompt+output buffer, ``cap`` the shared
      :func:`cap_draft_len` clamp (may be 0 near budget/capacity
      edges). Returns ``{slot: draft tokens}`` with every draft already
      within its row's cap.
    """

    kind = "none"
    # Whether this proposer drafts with the resident draft model — the
    # engine gates draft-cache admission prefills (and their dispatches)
    # on it, so a lookup proposer never pays the draft model's cost
    # even when a runtime is resident from an earlier A/B toggle.
    uses_draft_model = False
    # Whether the engine's pipelined spec dispatch may call
    # propose_wave against an OPTIMISTIC context (the true buffer plus
    # an unverified draft) while the verify is still in flight. Safe
    # only for proposers that are pure functions of the passed ctx —
    # the draft-model proposers keep per-slot device-side KV frontiers
    # that must track verified truth, so they stay synchronous.
    supports_runahead = False

    def eligible(self, params) -> bool:
        return draft_eligible(params)

    def on_admit(self, slot: int, prompt_len: int) -> None:  # noqa: ARG002
        return None

    def on_release(self, slot: int) -> None:  # noqa: ARG002
        return None

    def reset(self) -> None:
        return None

    def propose_wave(
        self, rows: Sequence[Tuple[int, Sequence[int], int]]
    ) -> Dict[int, List[int]]:
        raise NotImplementedError


class LookupProposer(SpecProposer):
    """PR 3's prompt-lookup drafting behind the seam: per-row host
    n-gram scans, no device work, greedy rows only. The exact prior
    spec path — ``spec_proposer='lookup'`` must reproduce it."""

    kind = "lookup"
    # Pure function of (ctx, cap): drafting from an optimistic context
    # is just another scan, so the pipelined dispatch may run ahead.
    supports_runahead = True

    def __init__(self, ngram_max: int) -> None:
        self.ngram_max = max(1, ngram_max)

    def propose_wave(self, rows):
        out: Dict[int, List[int]] = {}
        for slot, ctx, cap in rows:
            if cap <= 0:
                continue
            d = propose(ctx, self.ngram_max, cap)
            if d:
                out[slot] = d
        return out


class DraftModelProposer(SpecProposer):
    """Resident-draft-model drafting: delegates the batched draft
    dispatch (and the per-slot draft-KV frontier bookkeeping) to the
    engine-owned runtime (engine/spec_draft.py). Drafts sampled rows
    too — normal chat/RAG traffic runs at temperature ~0.2, and the
    acceptance rule is stream-preserving at any temperature."""

    kind = "draft_model"
    uses_draft_model = True

    def __init__(self, runtime) -> None:
        self._runtime = runtime

    def eligible(self, params) -> bool:
        return params.spec_decode is not False

    def on_admit(self, slot: int, prompt_len: int) -> None:
        self._runtime.on_admit(slot, prompt_len)

    def on_release(self, slot: int) -> None:
        self._runtime.on_release(slot)

    def reset(self) -> None:
        self._runtime.reset()

    def propose_wave(self, rows):
        return self._runtime.propose(rows)


class CombinedProposer(DraftModelProposer):
    """Lookup-then-draft: rows whose n-gram scan matches draft for free
    (copied spans, repetition loops); everything else takes the draft
    model's proposal. The draft dispatch still runs EVERY round — the
    catch-up chunk must feed each round's emitted tokens regardless, or
    the pending span would outgrow the fixed catch-up width."""

    kind = "combined"

    def __init__(self, ngram_max: int, runtime) -> None:
        super().__init__(runtime)
        self.ngram_max = max(1, ngram_max)

    def propose_wave(self, rows):
        model = self._runtime.propose(rows)
        out: Dict[int, List[int]] = {}
        for slot, ctx, cap in rows:
            if cap <= 0:
                continue
            d = propose(ctx, self.ngram_max, cap)
            if not d:
                d = model.get(slot, [])
            if d:
                out[slot] = d
        return out


class DraftTracker:
    """Pure-host bookkeeping of each slot's draft-model KV frontier.

    ``fed[slot]`` counts the tokens of the slot's proposer context
    already written into the draft KV cache (rows ``[0, fed)`` hold
    real sequence state; anything above is either this round's
    catch-up target or a previous round's rejected speculation). The
    ACCEPTANCE REWIND is this arithmetic: a verify that accepted ``n``
    draft tokens extends the context by ``n + 1`` (accepted + bonus)
    while ``fed`` stays at the pre-draft length, so the next round's
    catch-up span is exactly those ``n + 1 <= K + 1`` tokens — and
    writing them overwrites the rejected speculative rows in place,
    mirroring the target cache's rejected-row rule (the draft wrote K
    speculative rows past ``fed``; rows at the overwritten positions
    are replaced before any masked query attends them, rows above the
    new frontier are replaced by the round after).

    A row can fall out of the invariant only by NOT drafting while
    others kept the spec path (its cap hit 0 at the budget/capacity
    edge — monotone, it never drafts again): ``begin_round`` then
    drops its state instead of feeding an oversized span.
    """

    def __init__(self, draft_k: int) -> None:
        self.draft_k = max(1, draft_k)
        self._fed: Dict[int, int] = {}

    @property
    def catchup_width(self) -> int:
        """Static width of the catch-up chunk: a round emits at most
        ``accepted + bonus <= K + 1`` tokens per drafting row."""
        return self.draft_k + 1

    def on_admit(self, slot: int, prompt_len: int) -> None:
        self._fed[slot] = max(0, prompt_len)

    def on_release(self, slot: int) -> None:
        self._fed.pop(slot, None)

    def reset(self) -> None:
        self._fed.clear()

    def tracked(self, slot: int) -> bool:
        return slot in self._fed

    def begin_round(self, slot: int, ctx_len: int) -> Optional[Tuple[int, int]]:
        """(frontier, pending) for this round's catch-up, or None when
        the slot has no draft state (admitted while spec was off, or
        dropped below). A pending span outside ``[1, catchup_width]``
        retires the slot's state — it stopped drafting and can never
        re-enter the invariant."""
        fed = self._fed.get(slot)
        if fed is None:
            return None
        pending = ctx_len - fed
        if pending < 1 or pending > self.catchup_width:
            self._fed.pop(slot, None)
            return None
        return fed, pending

    def mark_fed(self, slot: int, ctx_len: int) -> None:
        """The catch-up chunk for this round was dispatched: the whole
        context is now in the draft cache."""
        self._fed[slot] = ctx_len


def cap_draft_len(draft_len: int, position: int, budget: int,
                  max_seq_len: int) -> int:
    """Clamp a row's draft length so the verify chunk stays inside both
    budgets:

    - ``budget - 1``: the dispatch emits accepted+1 tokens, so a draft
      longer than the remaining token budget wastes verify width past
      ``max_tokens`` (and the overshoot would only be discarded at
      emission);
    - ``max_seq_len - 2 - position``: the chunk writes KV rows at
      [position, position + draft_len] and the bonus token's next write
      position must stay < max_seq_len - 1 — past that the row positions
      would clamp onto the last cache row (the attention-window /
      capacity boundary).
    """
    return max(0, min(draft_len, budget - 1, max_seq_len - 2 - position))


def accepted_length(draft: Sequence[int], verified: Sequence[int]) -> int:
    """Host mirror of the device acceptance rule: the number of leading
    draft tokens equal to the verify outputs at the SAME index (verified
    [j] is the model's token after the prefix ending at draft[j-1], so
    draft[j] is accepted iff it equals verified[j] with all earlier
    positions accepted). Used by tests to pin the semantics the compiled
    cumprod implements."""
    n = 0
    for d, v in zip(draft, verified):
        if d != v:
            break
        n += 1
    return n


def record_draft_dispatch(program: str = "propose", n: int = 1) -> None:
    """Count resident-draft program launches: ``propose`` (one fused
    catch-up + K-step launch per spec round) or ``prefill`` (the
    admission chunk loop) — both sides of the draft model's cost."""
    _M_DRAFT_DISPATCHES.labels(program=program).inc(n)


def record_dispatch(drafted: int, accepted: int) -> None:
    """Account one (row, dispatch): ``drafted`` proposed tokens of which
    ``accepted`` were kept; tokens emitted is accepted + 1 (the bonus
    token from the first non-matching position is free)."""
    if drafted > 0:
        _M_DRAFTED.inc(drafted)
        if accepted > 0:
            _M_ACCEPTED.inc(accepted)
        _M_ACCEPTANCE.observe(accepted / drafted, trace_id=None)
    _M_DISPATCH_TOKENS.observe(accepted + 1, trace_id=None)


def record_adaptive_round(k: int) -> None:
    """Account one adaptive-K spec round dispatched at width ``k``."""
    _M_ADAPTIVE_ROUNDS.inc()
    _M_ADAPTIVE_K_SUM.inc(int(k))


def metrics_snapshot() -> dict:
    """Legacy flat-dict keys for the engine's ``metrics`` property
    (bench/tools read these without scraping Prometheus text)."""
    drafted = _M_DRAFTED.value
    accepted = _M_ACCEPTED.value
    return {
        "spec_drafted_tokens": drafted,
        "spec_accepted_tokens": accepted,
        "spec_acceptance_rate": (accepted / drafted) if drafted else 0.0,
        "spec_tokens_per_step": (
            _M_DISPATCH_TOKENS.sum / _M_DISPATCH_TOKENS.count
            if _M_DISPATCH_TOKENS.count
            else 0.0
        ),
        "spec_draft_dispatches": (
            _M_DRAFT_DISPATCHES.labels(program="propose").value
            + _M_DRAFT_DISPATCHES.labels(program="prefill").value
        ),
        "spec_adaptive_rounds": _M_ADAPTIVE_ROUNDS.value,
        "spec_adaptive_k_sum": _M_ADAPTIVE_K_SUM.value,
    }
