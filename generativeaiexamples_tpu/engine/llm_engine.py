"""The TPU LLM serving engine: continuous batching over a paged KV cache.

This is the in-repo replacement for the reference's NIM/TRT-LLM inference
container (reference: deploy/compose/docker-compose-nim-ms.yaml:2-22 —
"the GPU inference plane", SURVEY §2.5): an always-resident decoder with
slot-based continuous batching, so many HTTP requests share one compiled
decode loop.

Architecture (TPU-first), ONE serving path:
- Per-layer weight buffers, unrolled layers, and a shared page pool per
  layer (engine/kv_pages.py, docs/paged_kv.md); the model is reached
  through its family's three walks (models/registry.py). The pool is
  read by the ragged Pallas page kernel where the geometry allows and by
  the XLA gather elsewhere (CPU, unsupported geometry).
- ONE decode program, compiled once: ``[B] tokens × page pool →
  [K, B] next tokens`` — K = EngineConfig.decode_block steps fused into a
  single dispatch via lax.scan, with sampling fused in. B is the fixed
  slot count (EngineConfig.max_batch_size); requests claim/release slots —
  XLA sees static shapes forever, no recompiles at steady state.
- Every prompt prefills as fixed-shape ``prefill_chunk`` extend
  dispatches (engine/scheduler/shapes.py decides the shapes), so the
  executable set is bounded and a long prompt never stalls other
  slots' decode cadence more than one chunk.
- The decode loop runs on a dedicated thread; per-request token queues
  feed the server's SSE writers (server/api.py streams from them without
  touching the device). Host↔device traffic is one [K, B] int32 slab per
  decode dispatch — sampling happens on-device.
- Tensor parallelism: params/pool sharded over the ``model`` mesh axis
  (parallel/sharding.py); ICI allreduce inserted by XLA.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import itertools
import queue
import random
import threading
import time
import weakref
from typing import Any, Callable, Dict, Generator, Iterable, List, Optional, Sequence, Tuple

import numpy as np

from generativeaiexamples_tpu.config import EngineConfig
from generativeaiexamples_tpu.engine import compile_watch as compile_watch_mod
from generativeaiexamples_tpu.engine import dispatch_timeline as dispatch_timeline_mod
from generativeaiexamples_tpu.engine import kv_pages as kv_pages_mod
from generativeaiexamples_tpu.engine import prefix_cache as prefix_cache_mod
from generativeaiexamples_tpu.engine import request_snapshot as request_snapshot_mod
from generativeaiexamples_tpu.engine import scheduler as scheduler_mod
from generativeaiexamples_tpu.engine.scheduler import shapes as shapes_mod
from generativeaiexamples_tpu.engine import spec_decode as spec_decode_mod
from generativeaiexamples_tpu.engine.tokenizer import (
    IncrementalDecoder,
    TokenBlock,
    Tokenizer,
    load_tokenizer,
)
from generativeaiexamples_tpu.utils import faults as faults_mod
from generativeaiexamples_tpu.utils import flight_recorder
from generativeaiexamples_tpu.utils import get_logger
from generativeaiexamples_tpu.utils import hardware
from generativeaiexamples_tpu.utils import jax_env
from generativeaiexamples_tpu.utils import metrics as metrics_mod
from generativeaiexamples_tpu.utils import profiling
from generativeaiexamples_tpu.utils import provenance as provenance_mod
from generativeaiexamples_tpu.utils import slo as slo_mod
from generativeaiexamples_tpu.utils.resilience import EngineOverloaded, RequestPreempted

logger = get_logger(__name__)

# --------------------------------------------------------------------------- #
# Engine metric families (utils/metrics.py registry). Module-level and
# process-global: the engine is a singleton in production, and a scrape
# must see the full catalog (zero-valued) the moment this module imports
# — WITHOUT an engine ever being built. Registering here (no jax at
# module import) keeps that guarantee. The scheduling-phase histograms
# carry trace exemplars: the request's trace id is captured at submit()
# (the chain worker thread holds the span) and threaded to the reader
# thread's observations, so a slow TTFT bucket links to its trace.
_REG = metrics_mod.get_registry()
_M_REQUESTS = _REG.counter(
    "genai_engine_requests_total", "Requests submitted to the LLM engine."
)
_M_TOKENS = _REG.counter(
    "genai_engine_generated_tokens_total", "Tokens emitted by the decode loop."
)
_M_HANDOFFS = _REG.counter(
    "genai_stream_handoffs_total",
    "Items a token stream handed to its consumer (one per wake-up of "
    "the stream; an SSE handler writes each with one send).",
)
_M_HANDOFF_TOKENS = _REG.counter(
    "genai_stream_handoff_tokens_total",
    "Token ids those hand-offs carried: over genai_stream_handoffs_total "
    "it is tokens a hand-off, towards decode_block when the block "
    "hand-off engages, 1 when every token wakes the stream.",
)
_M_WRITE_LAG = _REG.histogram(
    "genai_stream_write_lag_seconds",
    "From the reader's put of a stream's oldest un-taken tokens to the "
    "handler's report that it has written their frames: the stream's leg "
    "of a token's way (the wake-up, the decoder, the SSE write). A "
    "client's frame gap is the engine's hand-off gap "
    "(genai_stream_handoff_gap_seconds) plus a difference of two of these.",
    buckets=metrics_mod.FAST_SECONDS_BUCKETS,
)
_M_DECODE_STEPS = _REG.counter(
    "genai_engine_decode_steps_total",
    "Decode steps executed (decode_block steps per dispatch).",
)
_M_WAVES = _REG.counter(
    "genai_engine_admission_waves_total", "Prefill admission waves dispatched."
)
_M_DECODE_DISPATCHES = _REG.counter(
    "genai_engine_decode_dispatches_total",
    "Decode/verify dispatches issued (one compiled-program launch each; "
    "a decode dispatch runs decode_block steps, a spec verify dispatch "
    "runs one multi-token step).",
)
_M_SAMPLER_FULL = _REG.counter(
    "genai_engine_sampler_full_vocab_dispatches_total",
    "Decode/verify dispatches that held a live row with temperature > 0 "
    "and top_p >= 1: the sampler's full-vocabulary draw ran in them "
    "(models/sampling.py skips it in every other dispatch).",
)
_M_PREFILL_CHUNKS = _REG.counter(
    "genai_engine_prefill_chunks_total",
    "Fixed-shape chunk dispatches run by chunked prefill.",
)
_M_QUEUE_WAIT = _REG.histogram(
    "genai_engine_queue_wait_seconds",
    "Submit -> slot-claimed wait (admission queueing).",
    # Bucket audit (PR 16): queue waits are a seconds-scale phase (a
    # full batch holds admissions for whole decode generations) — the
    # default preset burned its bottom half on sub-ms buckets this
    # family never fills while its 120 s ceiling saturated under
    # sustained overload. ~100x slower scale than the inter-token
    # family below, so it gets the slow preset.
    buckets=metrics_mod.SLOW_SECONDS_BUCKETS,
)
_M_TTFT = _REG.histogram(
    "genai_engine_ttft_seconds", "Submit -> first generated token."
)
_M_PREFILL_WAIT = _REG.histogram(
    "genai_engine_prefill_wait_seconds",
    "Slot-claimed -> first token (prefill + first readback).",
)
_M_TOKEN_LATENCY = _REG.histogram(
    "genai_engine_token_latency_seconds",
    "Per token, the interval since the request's previous token on the "
    "reader's clock. Since the block hand-off (PR 30) a readback's tokens "
    "arrive at one instant: the first of a block observes the whole gap "
    "since the previous block and the rest observe 0, so seven of eight "
    "(decode_block 8) or one of two observations say nothing. The gaps "
    "between hand-offs are genai_stream_handoff_gap_seconds.",
    buckets=(0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025,
             0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 10.0),
)
_M_READBACK = _REG.histogram(
    "genai_engine_readback_wait_seconds",
    "Reader-thread stall for a dispatch's device results, by kind.",
    ("kind",),
)
_M_SLOTS_IN_USE = _REG.gauge(
    "genai_engine_batch_slots_in_use",
    "Decode slots currently occupied by live requests.",
)
_M_SLOTS_CAPACITY = _REG.gauge(
    "genai_engine_batch_slots_capacity",
    "Configured decode slot count (max_batch_size).",
)
_M_KV_UTILIZATION = _REG.gauge(
    "genai_engine_kv_cache_utilization_ratio",
    "Fraction of KV-cache rows holding live sequence state.",
)
_M_ABORTS = _REG.counter(
    "genai_engine_aborts_total",
    "Requests aborted before completion (client disconnects, explicit "
    "abort() calls, stream-stop early exits) — their slots and prefix "
    "pins were released early.",
)
_M_OVERLOAD = _REG.counter(
    "genai_engine_overload_rejections_total",
    "submit() calls rejected with EngineOverloaded by the admission "
    "queue-depth cap (max_queued_requests).",
)
_M_QUEUE_DEPTH = _REG.gauge(
    "genai_engine_queue_depth",
    "Requests waiting in the admission queue (submitted, no slot yet).",
)
_M_WEDGED = _REG.gauge(
    "genai_engine_wedged",
    "1 while the dispatch-loop watchdog sees work outstanding with no "
    "dispatch progress past watchdog_stall_s (readiness flips unready).",
)
_M_SPEC_PIPE_ROLLBACKS = _REG.counter(
    "genai_engine_spec_pipeline_rollbacks_total",
    "Speculative runahead drafts invalidated by the verify readback "
    "(slot-rounds whose optimistic full-acceptance assumption missed; "
    "the row re-proposed from the true buffers — a host-work cost, "
    "never a correctness event).",
)
_M_SPEC_PIPE_CONFIRMED = _REG.counter(
    "genai_engine_spec_pipeline_confirmed_total",
    "Speculative runahead drafts confirmed by the verify readback "
    "(slot-rounds dispatched with zero proposal work on the critical "
    "path — the draft was proposed while the previous verify ran).",
)
_M_PAGED_ATTN = _REG.counter(
    "genai_engine_paged_attn_dispatches_total",
    "Paged-layout attention dispatches by serving path: path='kernel' "
    "(the ragged Pallas page-attention kernel, ops/page_attention.py — "
    "walks each row's live pages only) vs path='gather' (the "
    "XLA dequant-gather fallback reading the bucketed window). A paged "
    "engine whose geometry the kernel refuses logs the fallback loudly "
    "at startup and shows every decode dispatch under 'gather' here.",
    ("path",),
)
_M_PREFILL_TOKENS = _REG.counter(
    "genai_engine_prefill_tokens_total",
    "Prompt tokens run through the prefill and extend programs "
    "(padding and cached-prefix tokens excluded).",
)
_M_EXTEND_COMPUTED = _REG.counter(
    "genai_engine_extend_tokens_computed_total",
    "Token positions the prefill and extend programs computed: rows "
    "dispatched x width of each launch, padding rows and padding "
    "positions included. genai_engine_prefill_tokens_total over this "
    "is the live share of prefill compute.",
)
_M_STATE_RESETS = _REG.counter(
    "genai_engine_state_slot_resets_total",
    "Slots whose fixed per-slot state (recurrent state, window ring) "
    "was reset at admission, inside the prefill or first extend "
    "program; stays 0 for a model whose every layer is paged.",
)
_M_PREFIX_STATE_SAVES = _REG.counter(
    "genai_engine_prefix_state_saves_total",
    "Prefix entries whose fixed-state row was copied out of an admitting "
    "slot, between two chunks of its prefill (a family with "
    "state_row_keys: docs/prefix_cache.md).",
)
_M_PREFIX_STATE_RESTORES = _REG.counter(
    "genai_engine_prefix_state_restores_total",
    "Prefix hits whose saved fixed-state row was copied into the "
    "admitted slot before its first uncached chunk, in place of the "
    "slot's reset.",
)
_M_PREFIX_STATE_BYTES = _REG.counter(
    "genai_engine_prefix_state_bytes_total",
    "Bytes of fixed per-slot state copied between slot rows and store "
    "rows (one row's bytes a save or a restore).",
)
_M_CROSS_SKIPPED = _REG.counter(
    "genai_engine_prefill_cross_skipped_tokens_total",
    "Prompt tokens for which the layers past the shared-KV layer were "
    "NOT computed (a decoder-hybrid-decoder model computes them for a "
    "chunk's last position only).",
)
_M_MOE_PAIRS = _REG.counter(
    "genai_engine_moe_pairs_total",
    "(token, expert) pairs an expert layer routed, at the one step a "
    "dispatch reports (its last), by whether this chip HOLDS the expert "
    "('true': computed here; 'false': another chip's, left out).",
    ("held",),
)
_M_DSA_SELECTED = _REG.counter(
    "genai_engine_dsa_selected_tokens_total",
    "Cached tokens the learned selection of a sparse-attention layer "
    "let its queries read, at the one step a dispatch reports.",
)
_M_DSA_CONTEXT = _REG.counter(
    "genai_engine_dsa_context_tokens_total",
    "Cached tokens those queries had before them (the denominator of "
    "the selected share).",
)
_M_LATENT_READ = _REG.counter(
    "genai_engine_latent_read_tokens_total",
    "Cached tokens a dense latent-attention layer read for its queries "
    "(every token up to each query's own), at the one step a dispatch "
    "reports.",
)
_M_MSA_SELECTED = _REG.counter(
    "genai_engine_msa_pages_selected_total",
    "(Page, KV head) strips a block-sparse attention layer's learned "
    "selection named for its queries (first, top-k, local and open "
    "blocks), summed over the layers, at the one step a dispatch "
    "reports: what a decode step FETCHES.",
)
_M_MSA_LIVE = _REG.counter(
    "genai_engine_msa_pages_live_total",
    "(Page, KV head) strips live under those queries (what a walk of "
    "every live page would fetch): the denominator of the selected share.",
)
_M_MSA_SCORED = _REG.counter(
    "genai_engine_msa_blocks_scored_total",
    "Candidate (block, KV head) pairs the indexer scored from the page "
    "summaries for those queries.",
)
_M_MSA_POOLED = _REG.counter(
    "genai_engine_msa_pages_pooled_total",
    "Page summaries (the element-wise maximum of a complete page's "
    "cached keys) written, summed over the layers: one a page whose last "
    "token the dispatch wrote.",
)
_M_STATE_KERNEL_ROWS = _REG.counter(
    "genai_engine_state_kernel_rows_total",
    "Live rows whose recurrent state the step kernel advanced in place "
    "(ops/delta_rule.py), at the one step a dispatch reports; stays 0 "
    "where the XLA step serves. Beside the spans' state_rows it is the "
    "share of decode steps the kernel engages on.",
)
_M_LATENT_CHUNK_READS = _REG.counter(
    "genai_engine_latent_chunk_reads_total",
    "Latent-attention layers an extend dispatch read expanded, by path: "
    "kernel (ops/latent_attention.py latent_chunk_read: keys, values and "
    "scores stay in VMEM) or xla (the block loop). Beside the spans' "
    "latent_chunk_kernel_layers; the kernel's share is its engagement.",
    ("path",),
)
_M_MSA_CHUNK_READS = _REG.counter(
    "genai_engine_msa_chunk_reads_total",
    "Block-sparse attention layers an extend dispatch read, by path: "
    "kernel (ops/selected_chunk_read.py: scores, masks and "
    "probabilities stay in VMEM) or xla (the block loop). Beside the "
    "spans' msa_chunk_kernel_layers; the kernel's share is its engagement.",
    ("path",),
)
_M_WINDOW_READ = _REG.counter(
    "genai_engine_window_read_tokens_total",
    "Ring rows the window-attention layers read for their queries "
    "(each query's own position and the window before it, summed over "
    "the window layers), at the one step a dispatch reports.",
)
_M_FULL_READ = _REG.counter(
    "genai_engine_full_read_tokens_total",
    "Cached tokens the full-attention layers of a model that also has "
    "window layers read for their queries (every token up to each "
    "query's own), at the one step a dispatch reports. Beside "
    "window_read_tokens it is the window layers' share of attention reads.",
)
_M_EVA_WINDOW_READ = _REG.counter(
    "genai_engine_eva_window_tokens_read_total",
    "Exact keys the queries of a chunked-linearized-attention model read "
    "from their slots' open-window buffers (each query's own position and "
    "its window before it, summed over the layers), at the one step a "
    "dispatch reports.",
)
_M_EVA_SUMMARIES_READ = _REG.counter(
    "genai_engine_eva_summaries_read_total",
    "Chunk summaries of closed windows the same queries read from their "
    "pages, summed over the layers: beside eva_window_tokens_read, the "
    "compressed past's share of the rows a read covers.",
)
_M_EVA_SUMMARIES_WRITTEN = _REG.counter(
    "genai_engine_eva_summaries_written_total",
    "Chunk summaries written to the page pool (a chunk of an extend "
    "completed them, or a decode step wrote a chunk's last token), "
    "summed over the layers.",
)
_M_EVA_WINDOWS_CLOSED = _REG.counter(
    "genai_engine_eva_windows_closed_total",
    "Windows that closed (a row wrote its window's last token: its buffer "
    "restarts and the window's pages become visible), summed over the "
    "layers.",
)
_M_EVA_CHUNK_READS = _REG.counter(
    "genai_engine_eva_chunk_reads_total",
    "Chunked-linearized-attention layers an extend dispatch read, by "
    "path: kernel (ops/eva_read.py eva_chunk_read: buffer and summary "
    "pages read in place, scores, masks and probabilities stay in VMEM) "
    "or xla (the gathered, concatenated, masked read). Beside the spans' "
    "eva_chunk_kernel_layers; the kernel's share is its engagement.",
    ("path",),
)
# a family's step stats (models/registry.py ``stat_names``) that also feed
# a counter, by the stat's name: the engine knows mechanisms, not models
_STAT_COUNTERS = {
    "moe_pairs_held": _M_MOE_PAIRS.labels(held="true"),
    "moe_pairs_absent": _M_MOE_PAIRS.labels(held="false"),
    "dsa_tokens_selected": _M_DSA_SELECTED,
    "dsa_context_tokens": _M_DSA_CONTEXT,
    "latent_tokens_read": _M_LATENT_READ,
    "latent_chunk_kernel_layers": _M_LATENT_CHUNK_READS.labels(path="kernel"),
    "latent_chunk_xla_layers": _M_LATENT_CHUNK_READS.labels(path="xla"),
    "state_kernel_rows": _M_STATE_KERNEL_ROWS,
    "window_tokens_read": _M_WINDOW_READ,
    "full_tokens_read": _M_FULL_READ,
    "msa_pages_selected": _M_MSA_SELECTED,
    "msa_pages_live": _M_MSA_LIVE,
    "msa_blocks_scored": _M_MSA_SCORED,
    "msa_pages_pooled": _M_MSA_POOLED,
    "msa_chunk_kernel_layers": _M_MSA_CHUNK_READS.labels(path="kernel"),
    "msa_chunk_xla_layers": _M_MSA_CHUNK_READS.labels(path="xla"),
    "eva_window_tokens_read": _M_EVA_WINDOW_READ,
    "eva_summaries_read": _M_EVA_SUMMARIES_READ,
    "eva_summaries_written": _M_EVA_SUMMARIES_WRITTEN,
    "eva_windows_closed": _M_EVA_WINDOWS_CLOSED,
    "eva_chunk_kernel_layers": _M_EVA_CHUNK_READS.labels(path="kernel"),
    "eva_chunk_xla_layers": _M_EVA_CHUNK_READS.labels(path="xla"),
}
_M_SSM_DISPATCHES = _REG.counter(
    "genai_engine_ssm_dispatches_total",
    "Program launches that advanced a recurrent state, by path: "
    "'scan' (prefill and extend: a selective scan over the chunk) or "
    "'step' (decode: one fused update a step).",
    ("path",),
)

_NO_STOP_IDS: frozenset = frozenset()  # what ends a request that set ``ignore_eos``


@dataclasses.dataclass
class SamplingParams:
    temperature: float = 0.2  # reference default, server.py:83
    top_p: float = 0.7  # server.py:84
    max_tokens: int = 1024  # server.py:85
    stop: Tuple[str, ...] = ()
    seed: int = 0
    # Session/prefix hint (chain name, collection, conversation id...):
    # lets the prefix KV cache keep an active session's cached preamble
    # alive under LRU pressure between turns. Purely advisory — prefix
    # matching itself is content-addressed over the prompt tokens.
    prefix_hint: Optional[str] = None
    # Per-request speculative-decoding override: None follows the
    # engine's spec_decode_enable, False opts this request out of
    # drafting (it still shares the verify dispatch as a single-token
    # row), True is advisory (a no-op when the engine has spec off).
    # Only greedy (temperature<=0) rows ever draft.
    spec_decode: Optional[bool] = None
    # A stop id is an ORDINARY token: it reaches the stream and the
    # request ends at ``max_tokens`` (or at its slot's capacity). Under a
    # byte-level vocabulary of a few hundred ids a sampled answer would
    # otherwise end wherever chance draws one of the stop ids.
    ignore_eos: bool = False


class _TokenQueue:
    """A request's ``out_queue``: generated ids in order, closed by
    ``_END``. The reader puts one request's tokens of one readback in
    ONE ``put_many`` and a stream takes whatever is held in ONE
    ``take_all``, so the hand-off between the two threads is a block by
    construction, not by luck of thread timing. ``get`` hands out one
    item at a time (``queue.Empty`` on a timeout, as a queue.Queue)."""

    def __init__(self) -> None:
        self._items: "collections.deque[Optional[int]]" = collections.deque()
        self._ready = threading.Condition(threading.Lock())
        # wall time of the oldest put nobody has taken (0.0: none, or a
        # producer that gives no time), and of the one take_all took last
        self._t_put = 0.0
        self.t_taken = 0.0

    def put(self, item: Optional[int]) -> None:
        self.put_many((item,))

    def put_many(self, items: Sequence[Optional[int]], t_put: float = 0.0) -> None:
        with self._ready:
            self._items.extend(items)
            if not self._t_put:
                self._t_put = t_put
            self._ready.notify()

    def _wait(self, timeout: Optional[float]) -> None:
        if not self._ready.wait_for(lambda: self._items, timeout):
            raise queue.Empty

    def get(self, timeout: Optional[float] = None) -> Optional[int]:
        with self._ready:
            self._wait(timeout)
            return self._items.popleft()

    def take_all(self, timeout: Optional[float] = None) -> List[Optional[int]]:
        with self._ready:
            self._wait(timeout)
            items = list(self._items)
            self._items.clear()
            self.t_taken, self._t_put = self._t_put, 0.0
            return items


@dataclasses.dataclass
class _Request:
    rid: int
    prompt_ids: List[int]
    params: SamplingParams
    out_queue: _TokenQueue = dataclasses.field(default_factory=_TokenQueue)
    slot: int = -1
    # Effective sampling seed: params.seed when given, else a fresh random
    # draw at submit time — unseeded requests must NOT share a key stream
    # (two identical unseeded prompts should sample different completions).
    sampling_seed: int = 0
    # Scheduling timeline (time.time()): TTFT decomposes into queue wait
    # (submit -> slot claimed) + prefill/readback (slot -> first token).
    t_submit: float = 0.0
    t_admit: float = 0.0
    t_last_token: float = 0.0
    # The device clock (engine/dispatch_timeline.py DeviceClock) at this
    # request's previous stream hand-off; rows handed tokens by the same
    # launch share ONE object. None before the first, and with the
    # timeline off.
    gap_clock: Optional[tuple] = None
    gap_owed: float = 0.0  # <= 0: device time an earlier gap held as host time
    # Trace id (32 hex chars) active at submit time — observations for
    # this request happen on engine threads with no span stack, so the
    # exemplar context rides the request object instead.
    trace_hex: Optional[str] = None
    # Prefix-cache entry this request was admitted against, pinned
    # (refcounted) from match until its fetch copy is dispatched — the
    # window where an eviction could rewrite the store rows the fetch
    # reads — then released in _admit (decode itself never reads the
    # store). prefix_len is the matched row count (may be shorter than
    # the entry — radix partial match).
    prefix_entry: Optional[object] = None
    prefix_len: int = 0
    # the entry this request entered through, kept (unpinned) past the
    # funding step for a family whose entries carry a fixed-state row:
    # the row to restore from, and the ticket a deeper insert takes over
    prefix_via: Optional[object] = None
    # Flight-recorder record captured at submit: slot release (where the
    # paged layout frees the request's pages) happens AFTER finish_rid
    # unmaps the rid, so the page_free event must reach the record
    # directly — it lands in the timeline right after "finish", which is
    # when the free actually occurs.
    flight_rec: Optional[object] = None
    position: int = 0  # next absolute position to decode
    generated: int = 0
    # Every generated token in order (reader thread appends; includes
    # stop tokens the out_queue suppresses). This is the request's
    # resumable transcript: a drain checkpoint spools it, and restore
    # re-seeds the stream + the next decode input from its tail —
    # emitted[-1] is exactly the token whose KV row has not been
    # written yet (engine/request_snapshot.py).
    emitted: List[int] = dataclasses.field(default_factory=list)
    # The stream's backlog in two integers: ids the reader has put on
    # out_queue, and ids a handler has written to its socket (None until
    # a handler reports: a consumer without one has no backlog to read).
    queued: int = 0
    written: Optional[int] = None
    cancelled: bool = False
    finished: bool = False  # set by the reader thread once _END is queued
    error: Optional[BaseException] = None


_END = None  # sentinel on out_queue


def _next_stream_items(out_q, stall_s, deadline):
    """One bounded wait for everything the stream holds next (iter_ids
    and _stream_from): a list of ids, ``_END`` last when the stream is
    over. ``stall_s`` bounds THIS wait only — the stream_timeout_s stall
    semantics, where a healthy long stream never times out. ``deadline``
    is an absolute whole-stream budget (per-request deadlines): expiry
    is checked BEFORE waiting, because a decode emitting tokens faster
    than any wait's floor never sees queue.Empty and would otherwise
    outrun its budget to max_tokens. Exactly one of the two is
    non-None."""
    if deadline is None:
        wait = stall_s
    else:
        wait = deadline - time.time()
        if wait <= 0:
            raise TimeoutError("LLM engine timed out")
    try:
        return out_q.take_all(timeout=wait)
    except queue.Empty:
        raise TimeoutError("LLM engine timed out") from None


def _sampler_full_rows(requests: Iterable["_Request"]) -> int:
    """Rows of the decode dispatch about to launch that force the
    sampler's full-vocabulary draw (temperature > 0 and top_p >= 1, in
    float32 as the device reads them), counted into
    genai_engine_sampler_full_vocab_dispatches_total."""
    rows = sum(
        1 for r in requests
        if np.float32(r.params.temperature) > 0 and np.float32(r.params.top_p) >= 1
    )
    if rows:
        _M_SAMPLER_FULL.inc()
    return rows


def _update_slots(tokens, positions, temps, topps, seeds, slots, toks, poss, ts, ps, ss):
    """Admission: inject freshly prefilled requests' state into the
    device-resident arrays (dispatched into the decode chain — ordering
    is by dispatch, still no sync). Duplicate padded slots scatter
    identical values, which is well-defined. jit WITHOUT donation — the
    tokens array fed in can be a decode output whose buffer the reader
    thread is still reading back.
    """
    return (
        tokens.at[slots].set(toks),
        positions.at[slots].set(poss),
        temps.at[slots].set(ts),
        topps.at[slots].set(ps),
        seeds.at[slots].set(ss),
    )


def _prefix_store_extra_slots(cfg: EngineConfig) -> int:
    """Full-capacity strips of pool the prefix cache's entries may hold,
    as far as the config alone can tell. One rule shared by the pool
    sizing and the fit planner so their HBM estimates can't diverge."""
    if cfg.prefix_cache_enable != "off":
        return cfg.prefix_cache_slots
    return 0


def _validate_resilience_knobs(cfg: EngineConfig) -> None:
    """Validate the engine's resilience knobs (host-side)."""
    if cfg.stream_timeout_s <= 0:
        raise ValueError(
            f"stream_timeout_s must be > 0, got {cfg.stream_timeout_s}"
        )
    if cfg.quiesce_timeout_s <= 0:
        raise ValueError(
            f"quiesce_timeout_s must be > 0, got {cfg.quiesce_timeout_s}"
        )
    if cfg.max_queued_requests < 0:
        raise ValueError(
            f"max_queued_requests must be >= 0 (0 = unbounded), got "
            f"{cfg.max_queued_requests}"
        )
    if 0 < cfg.max_queued_requests < cfg.max_batch_size:
        # warmup() enqueues whole padded admission waves (up to
        # max_batch_size requests at once) under hold_admissions; a cap
        # below that would fail warmup instead of shedding load.
        raise ValueError(
            f"max_queued_requests ({cfg.max_queued_requests}) must be >= "
            f"max_batch_size ({cfg.max_batch_size}) so warmup waves fit "
            f"the admission queue"
        )
    if cfg.watchdog_stall_s < 0:
        raise ValueError(
            f"watchdog_stall_s must be >= 0 (0 disables), got "
            f"{cfg.watchdog_stall_s}"
        )


def _start_host_copy(array) -> None:
    """Kick off an async device→host copy if the backend supports it."""
    try:
        array.copy_to_host_async()
    except (AttributeError, NotImplementedError):
        pass


class LLMEngine:
    """Slot-based continuous-batching engine around models/llama.py."""

    def __init__(
        self,
        config: Optional[EngineConfig] = None,
        tokenizer: Optional[Tokenizer] = None,
        mesh=None,
    ):
        import jax
        import jax.numpy as jnp

        from generativeaiexamples_tpu.models import llama
        from generativeaiexamples_tpu.models.hf_loader import config_from_hf
        from generativeaiexamples_tpu.parallel.mesh import (
            create_mesh,
            mesh_context,
        )
        from generativeaiexamples_tpu.parallel.sharding import shard_params

        cfg = config or EngineConfig()
        self.engine_config = cfg
        # Compile-path observability (engine/compile_watch.py): created
        # before ANY compiled step is built so every jit family
        # dispatches through its wrapper.
        self._compile_watch = compile_watch_mod.CompileWatch()

        # --- model config + weights --------------------------------------
        from generativeaiexamples_tpu.models import registry as model_registry

        model_cfg = None
        if cfg.checkpoint_path:
            model_cfg = config_from_hf(cfg.checkpoint_path)
        if model_cfg is None:
            family, model_cfg = model_registry.resolve(cfg.model_config_name)
        else:
            family = model_registry.family_of(model_cfg)
        self.model_config = model_cfg
        # The model family (models/registry.py): the paged step programs,
        # the cache pytree and the memory plan all go through it. What the
        # pools hold (layers, KV heads, head size) is the family's to say:
        # a fixed-state family pages ONE layer and keeps the rest per slot.
        self._family = family
        self._kv_shape = family.paged_kv_shape(model_cfg)
        self._fixed_state = bool(family.fixed_state)
        self._span_fields = dict(family.span_fields(model_cfg))
        # why a request snapshot cannot carry a request of this family
        # (None: it can), said where one is taken (request_snapshot.py)
        self._snapshot_refusal = None
        if self._fixed_state:
            self._snapshot_refusal = (
                "keeps a fixed per-slot state beside the page pool, which a "
                "request snapshot cannot carry"
            )
        elif not family.snapshot_pages:
            self._snapshot_refusal = (
                f"is of the {family.name} family, whose page pools are not "
                "the per-layer K and V pages a request snapshot's payload "
                "carries (snapshot_pages, models/registry.py)"
            )
        self._stat_names = tuple(family.stat_names)
        if cfg.kv_cache_dtype not in ("bfloat16", "int8", "int4"):
            raise ValueError(
                f"kv_cache_dtype must be 'bfloat16', 'int8', or 'int4', "
                f"got {cfg.kv_cache_dtype!r}"
            )
        cfg = self._validate_family(cfg, mesh)
        self.engine_config = cfg
        self.tokenizer = tokenizer or load_tokenizer(cfg.tokenizer_path or cfg.checkpoint_path)
        # Sample only ids the tokenizer can represent: with the byte-level
        # fallback tokenizer (~260 ids) under a 128k-vocab head (random-init
        # serving, no checkpoint), unrestricted sampling yields ids that
        # decode to empty strings — streams look blank and stop tokens are
        # unreachable. A smaller head is never sliced (min with model vocab).
        tok_vocab = getattr(self.tokenizer, "vocab_size", 0) or model_cfg.vocab_size
        self._sample_vocab = min(model_cfg.vocab_size, max(tok_vocab, 1))

        dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32, "float16": jnp.float16}[
            cfg.dtype
        ]
        if cfg.prefix_cache_enable not in ("auto", "off"):
            raise ValueError(
                f"prefix_cache_enable must be auto|off, got "
                f"{cfg.prefix_cache_enable!r}"
            )
        if cfg.prefix_cache_slots < 0:
            raise ValueError(
                f"prefix_cache_slots must be >= 0, got "
                f"{cfg.prefix_cache_slots}"
            )
        _validate_resilience_knobs(cfg)
        spec_decode_mod.validate_config(cfg)
        kv_pages_mod.validate_config(cfg)
        scheduler_mod.validate_config(cfg)
        if mesh is not None:
            self._mesh = mesh
        else:
            self._mesh = create_mesh(
                tensor_parallelism=self._resolve_parallelism(cfg, model_cfg)
            )
        logger.info("LLM engine mesh: %s", dict(self._mesh.shape))
        dev0 = self._mesh.devices.reshape(-1)[0]
        # Unknown TPU parts fail here, not as a wrong MFU later.
        hardware.configure_peaks(dev0.platform, dev0.device_kind)
        self._check_memory_budget(cfg, model_cfg)

        # Serving layout: unrolled per-layer weight/cache buffers — scan
        # xs/carry slices feeding Pallas calls cost an HBM copy each
        # (~20% of decode step time measured at B=32); per-layer buffers
        # avoid the slicing entirely. int8 and int4 KV both ride the
        # quantized pool machinery (scale planes, exact-operand
        # kernels); int4 additionally packs two values per byte.
        self._kv_quant = cfg.kv_cache_dtype in ("int8", "int4")
        self._kv_packed = cfg.kv_cache_dtype == "int4"
        # TP kernel path (VERDICT r2 #1): on a PURE tensor-parallel mesh
        # (the serving topology — mesh.size == model axis), the Pallas
        # kernels run on each device's local Megatron tile via shard_map
        # (parallel/tp_kernels.py) instead of falling back to XLA paths.
        # The reference's inference plane keeps its TRT-LLM kernels at
        # any INFERENCE_GPU_COUNT (docker-compose-nim-ms.yaml:20); this
        # is the TPU equivalent. GENAI_TPU_TP_KERNELS: auto (TPU only) |
        # off | interpret (virtual CPU meshes — tests/dryrun execute the
        # same shard_map paths in Pallas interpret mode).
        import os as _os

        from generativeaiexamples_tpu.parallel import tp_kernels

        model_shards = self._mesh.shape.get("model", 1)
        pure_tp = model_shards > 1 and self._mesh.size == model_shards
        tp_env = _os.environ.get("GENAI_TPU_TP_KERNELS", "auto").lower()
        if tp_env in ("0", "off", "false", "no"):
            tp_want, tp_interpret = False, False
        elif tp_env == "interpret":
            tp_want, tp_interpret = True, jax.default_backend() != "tpu"
        else:  # auto
            tp_want, tp_interpret = jax.default_backend() == "tpu", False
        tp_eligible = (
            pure_tp
            and tp_want
            and tp_kernels.supports_model_config(model_cfg, model_shards)
        )
        self._tp = (
            tp_kernels.TPContext(self._mesh, model_shards, tp_interpret)
            if tp_eligible
            else None
        )
        if self._tp is not None:
            logger.info(
                "TP kernel path enabled: %d-way shard_map tiles%s",
                model_shards,
                " (interpret)" if tp_interpret else "",
            )
        if self._kv_packed and model_cfg.head_dim % 2:
            raise ValueError(
                "kv_cache_dtype='int4' packs two values per byte along "
                f"head_dim, which must be even (got {model_cfg.head_dim})"
            )
        # Per-shard pack layout under the TP kernel path (ops/quant.py):
        # every NamedSharding slice of a pack is then a self-contained
        # kernel tile. Global-layout packs everywhere else.
        pack_shards = (
            model_shards
            if (self._tp is not None and cfg.quantization in ("int8", "w8a8"))
            else 1
        )
        # The single-device Pallas weight-streaming flag: opaque to GSPMD,
        # so plain jit uses it only when the model axis is unsharded.
        # Sharded meshes route packs through self._tp (shard_map tiles)
        # when eligible, XLA dequant otherwise. Captured per engine
        # instance and threaded through every trace. quantization="w8a8"
        # selects the int8-MXU kernel (per-token activation quant, 2x
        # issue rate) for decode-shaped calls.
        kernel_ok = (
            jax.default_backend() == "tpu" and self._mesh.shape.get("model", 1) == 1
        )
        if kernel_ok:
            self._quant_kernel = "w8a8" if cfg.quantization == "w8a8" else True
        elif cfg.quantization == "w8a8" and self._tp is not None:
            # TP shard_map tiles consume the flag directly (tp_kernels
            # packed_matmul_tp w8a8=...); without it the configured w8a8
            # mode silently served weight-only semantics under TP.
            self._quant_kernel = "w8a8"
        elif cfg.quantization == "w8a8":
            # No Pallas path (CPU backend, or a sharded mesh without the
            # TP kernel context) — serve w8a8 through the pure-XLA
            # int8-dot so the configured numerics contract holds
            # everywhere the config does, rather than silently
            # downgrading to weight-only semantics.
            self._quant_kernel = "w8a8_xla"
            logger.info(
                "quantization='w8a8' serving via the XLA int8-dot path "
                "(no Pallas kernel on this mesh/backend)."
            )
        else:
            self._quant_kernel = False
        # Stage weights on the HOST: materializing bf16 llama3-8b (16 GB)
        # on a 16 GB chip before quantization would OOM — init and
        # quantize on CPU, then device-put the final (often int8,
        # half-size) arrays into HBM once. Checkpoints STREAM instead
        # (VERDICT r2 missing #3): each layer is quantized and
        # device-placed as its safetensors tensors complete, so peak
        # host memory is ~one shard — the only load path that scales to
        # 70B-class checkpoints (~140 GB on disk, reference
        # docs/support-matrix.md:63-80) on a normal host.
        self._streamed_load = bool(cfg.checkpoint_path)
        if self._streamed_load:
            from generativeaiexamples_tpu.models.hf_loader import (
                load_params_layered_streaming,
            )

            self.params = load_params_layered_streaming(
                cfg.checkpoint_path,
                model_cfg,
                dtype,
                quantization=cfg.quantization,
                mesh=self._mesh,
                tp_shards=pack_shards,
            )
            logger.info(
                "Loaded LLM weights (streaming) from %s", cfg.checkpoint_path
            )
        else:
            with jax.default_device(jax_env.host_device()):
                if cfg.quantization in ("int8", "w8a8"):
                    # Random-init path: draw packed int8 weights directly —
                    # generating f32 normals and quantizing costs ~15 min for
                    # 8B on the single host core.
                    from generativeaiexamples_tpu.ops.quant import init_packed_params_int8

                    params = init_packed_params_int8(
                        model_cfg, 0, dtype, tp_shards=pack_shards
                    )
                else:
                    params = family.init_params(model_cfg, 0, dtype)
            logger.warning(
                "LLM engine running with random-init weights (no checkpoint)."
            )
            if self._mesh.size > 1:
                from generativeaiexamples_tpu.parallel.sharding import (
                    shard_params_layered,
                )

                # Multi-device: GSPMD-shard the stacked tree first (bulk
                # transfers), split per layer on device, then pin each
                # per-layer leaf to its explicit Megatron spec
                # (slice-inferred shardings are XLA's choice, not a
                # contract).
                with mesh_context(self._mesh):
                    params = shard_params(params, self._mesh)
                    self.params = shard_params_layered(
                        llama.consume_split_params_layers(params), self._mesh
                    )
            else:
                # Transfer the STACKED tree (a dozen big buffers, not ~130
                # split leaves) with an explicit device:
                # device_put with no target is a NO-OP for committed arrays,
                # so the host-staged (CPU-committed) leaves would silently
                # stay behind and be re-shipped on every dispatch. Then split
                # per layer on device (HBM-to-HBM slices).
                device = self._mesh.devices.reshape(-1)[0]
                params = jax.device_put(params, device)
                # (a family's place_params may consume params; drop the local
                # ref so each buffer it pops frees immediately)
                self.params = family.place_params(params)
            del params

        # --- the KV cache: a page pool -----------------------------------
        # One shared [P, page, Hkv, Dh] buffer per layer holds every
        # request's K/V and the prefix cache's entries (refcounted pool
        # pages — zero-copy hits). Auto-sizing gives each decode slot
        # and each prefix entry one full-capacity strip of pages.
        self.num_slots = cfg.max_batch_size
        self.max_seq_len = min(cfg.max_seq_len, model_cfg.max_seq_len)
        # Every shape a dispatch can take (engine/scheduler/shapes.py).
        # A family that registered a walk over a packed token axis is
        # sent its prefill waves packed; one whose extend walk follows
        # each row's own context is given ONE window, capacity
        # (models/registry.py). ``page_kernel`` is set once resolved.
        self.shapes = shapes_mod.ShapePlan(
            prefill_chunk=cfg.prefill_chunk,
            page_size=cfg.page_size,
            max_seq_len=self.max_seq_len,
            num_slots=self.num_slots,
            prefill_wave_tokens=cfg.prefill_wave_tokens,
            decode_block=max(1, cfg.decode_block),
            fixed_state=self._fixed_state,
            packed=family.extend_packed is not None,
            extend_reads_window=bool(family.extend_reads_window),
            page_kernel=False,
        )
        prefix_slots = _prefix_store_extra_slots(cfg)
        self._pool_pages = kv_pages_mod.pool_pages(
            cfg, self.max_seq_len, prefix_slots
        )
        kv_pages_mod.validate_runtime(
            cfg.page_size, self.max_seq_len, self._pool_pages
        )
        # A fixed-state family the prefix store can carry keeps the
        # store's rows BEHIND the decode slots' in the same per-slot
        # arrays: ticket k of the index is row num_slots + k, and saving
        # or restoring a prefix's state is one row copied to another
        # (_build_steps ``prefix_state_copy``).
        self._state_store_rows = (
            prefix_slots if self._fixed_state and family.state_row_keys else 0
        )
        pool = family.init_paged_cache(
            model_cfg, self._pool_pages, cfg.page_size,
            self.num_slots + self._state_store_rows,
            dtype, quantized=self._kv_quant, packed=self._kv_packed,
            # shard_kv_pool below puts the pools' heads on the model axis
            head_sharded=self._mesh.size > 1,
        )
        # how the quantised pool stores its scale planes, read off the
        # pool it built (models/llama.py kv_scale_plane_shape decides)
        self._kv_scale_plane = (
            tuple(pool[0]["ks"].shape[1:]) if self._kv_quant else None
        )
        if self._mesh.size > 1:
            from generativeaiexamples_tpu.parallel.sharding import (
                shard_kv_pool,
            )

            with mesh_context(self._mesh):
                self._cache = shard_kv_pool(
                    pool, self._mesh, quantized=self._kv_quant
                )
        else:
            self._cache = jax.device_put(
                pool, self._mesh.devices.reshape(-1)[0]
            )
        del pool
        self._kv_alloc = kv_pages_mod.PageAllocator(
            self._pool_pages, cfg.page_size
        )
        self._max_pages_per_slot = kv_pages_mod.pages_for_tokens(
            self.max_seq_len, cfg.page_size
        )
        # Dispatch-overrun slack the admission reservation funds:
        # in-flight decode blocks and spec-verify chunks keep
        # writing up to a block past a request's budget before the
        # eager release lands. The spec term uses the EFFECTIVE
        # draft width (one rule with the verify program and every
        # cap_draft_len caller — spec_decode.effective_draft_len),
        # so a draft-model K override can never propose past the
        # funded reservation (tests/test_kv_pages.py pins it).
        self._page_slack = (
            cfg.decode_block + spec_decode_mod.effective_draft_len(cfg) + 1
        )
        logger.info(
            "paged KV cache: %d pages x %d tokens (%d-slot capacity "
            "equivalent, scratch page reserved)",
            self._pool_pages, cfg.page_size,
            (self._pool_pages - 1) // self._max_pages_per_slot,
        )
        if self._fixed_state:
            plan = kv_pages_mod.cache_plan(
                self._pool_pages, cfg.page_size,
                self.num_slots + self._state_store_rows,
                paged_bytes_per_token=(
                    self._kv_shape.bytes_per_token
                    or kv_pages_mod.page_bytes(
                        self._kv_shape.num_layers, 1,
                        self._kv_shape.num_kv_heads,
                        self._kv_shape.head_dim, quantized=False,
                    )
                ),
                fixed_bytes_per_slot=family.fixed_state_bytes_per_slot(
                    model_cfg
                ),
            )
            logger.info(
                "fixed per-slot state beside the pool: %.1f MB a slot x "
                "(%d slots + %d prefix-store rows) = %.2f GB (pool %.2f GB "
                "for %d paged layer(s))",
                plan.fixed_bytes_per_slot / 1e6, self.num_slots,
                self._state_store_rows,
                plan.fixed_bytes / 1e9, plan.paged_bytes / 1e9,
                self._kv_shape.num_layers,
            )
            self._state_row_bytes = plan.fixed_bytes_per_slot
        # The ragged page kernel (ops/page_attention.py), resolved per
        # executable family: decode (single-query rows) and spec verify
        # (K+1-wide rows), each behind its geometry probe with a LOUD
        # fallback to the XLA dequant gather.
        self._paged_kernel: Optional[str] = None
        self._kv_pages_a_step = 1
        self._kv_score_rows = 0  # set where ops/page_attention.py reads the pool
        self._paged_verify_kernel: Optional[str] = None
        self._paged_extend_kernel: Optional[str] = None
        self._resolve_paged_kernel(cfg, model_cfg)
        self.shapes = dataclasses.replace(
            self.shapes, page_kernel=self._paged_kernel is not None
        )
        # kernels the family brings beside the engine's own
        # (models/registry.py ``resolve_kernels``), by the same rule of
        # platform: compiled on one TPU device, interpreted on request
        self._family_kernels = dict(family.resolve_kernels(
            model_cfg,
            "interpret" if cfg.paged_kernel == "interpret"
            else "compiled" if (
                cfg.paged_kernel != "off"
                and jax.default_backend() == "tpu"
                and jax.device_count() == 1 and self._tp is None
            ) else None,
        ))

        # One line naming every resolved kernel path: auto modes fall
        # back to XLA quietly off-TPU (right for tests), so a smoke run
        # asserts the outcome from here (chip_smoke.py).
        logger.info(
            "resolved kernel paths: quant_kernel=%s "
            "paged_kernel=%s paged_verify_kernel=%s "
            "paged_extend_kernel=%s tp_kernels=%s kv_scales=%s%s "
            "(backend=%s, devices=%d)",
            self._quant_kernel, self._paged_kernel,
            self._paged_verify_kernel, self._paged_extend_kernel,
            f"{self._tp.shards}-way" if self._tp is not None else None,
            self._kv_scale_layout(),
            "".join(f" {k}={v}" for k, v in sorted(self._family_kernels.items())),
            jax.default_backend(), jax.device_count(),
        )
        # --- compiled steps ---------------------------------------------
        self._build_steps()
        self._dtype = dtype
        self._init_spec_proposer(cfg)
        self._init_prefix_cache(cfg)
        self._init_scheduler_state(cfg)

    def _kv_scale_layout(self) -> Optional[str]:
        """The layout of the quantised pool's scale planes as built, for
        the ``resolved kernel paths:`` line: ``lane_dense`` ([P, page *
        Hkv / 128, 128]: 4 KB scale blocks, two int8 pages a grid step),
        ``token_major`` ([P, page, Hkv]), None for a pool without."""
        if self._kv_scale_plane is None:
            return None
        token_major = (self.engine_config.page_size, self._kv_shape.num_kv_heads)
        return "token_major" if self._kv_scale_plane == token_major else "lane_dense"

    def _validate_family(self, cfg: EngineConfig, mesh) -> EngineConfig:
        """Refuse, at engine build, what the model family does not
        DECLARE it can be served with (models/registry.py,
        docs/model_registry.md): a sharded mesh (``sharded``), a weight
        or pool format its walks do not read (``weight_formats``,
        ``kv_formats``), speculation without a verify walk
        (``verify_paged``) and, for a family with fixed per-slot state,
        a prefix store it names no state rows for (``state_row_keys``).
        One clear error each; no silent fallback to a path that would
        serve such a model wrongly. A family that has pages only keeps
        the prefix store. Request snapshots are refused where they are
        taken (``drain``, ``restore_snapshot``): the spool directory
        always has a default, so its being set says nothing. Returns
        the config with ``tensor_parallelism=-1`` resolved to 1 for a
        family that cannot be sharded (one device serves it)."""
        import dataclasses as _dc

        fam = self._family
        name = f"{fam.name} model {cfg.model_config_name!r}"

        def refuse(what: str, lacks: str, knob: str) -> None:
            if fam.fixed_state:
                raise ValueError(
                    f"{name} keeps a fixed per-slot state beside the page "
                    f"pool, which {what} cannot carry; {knob}"
                )
            raise ValueError(
                f"{name} cannot be served with {what}: its family "
                f"{lacks} (models/registry.py); {knob}"
            )

        mesh_size = mesh.size if mesh is not None else 1
        if not fam.sharded and (cfg.tensor_parallelism > 1 or mesh_size > 1):
            refuse("a sharded mesh", "declares no sharded walk",
                   "set tensor_parallelism=1")
        if fam.fixed_state:
            prefix_cache_mod.require_paged_state(name, cfg, fam.state_row_keys)
        if fam.verify_paged is None:
            spec_decode_mod.require_verify_walk(name, cfg, fam.fixed_state)
        if cfg.quantization not in ("", "none", *fam.weight_formats):
            refuse(f"quantization={cfg.quantization!r} (no packed walk)",
                   f"reads weight formats {list(fam.weight_formats)} "
                   "beside plain weights", "set quantization='none'")
        if cfg.kv_cache_dtype not in ("bfloat16", *fam.kv_formats):
            refuse(f"kv_cache_dtype={cfg.kv_cache_dtype!r}",
                   f"reads pool formats {list(fam.kv_formats)} beside "
                   "bfloat16", "set kv_cache_dtype='bfloat16'")
        if not fam.sharded and cfg.tensor_parallelism == -1:
            cfg = _dc.replace(cfg, tensor_parallelism=1)
        return cfg

    def _build_draft_runtime(self, cfg: EngineConfig):
        """Construct the resident-draft runtime (engine/spec_draft.py)
        against this engine's mesh/slots/ladders."""
        from generativeaiexamples_tpu.engine import spec_draft as spec_draft_mod

        return spec_draft_mod.DraftRuntime(
            cfg,
            mesh=self._mesh,
            compile_watch=self._compile_watch,
            dtype=self._dtype,
            sample_vocab=self._sample_vocab,
            shapes=self.shapes,
        )

    def _init_spec_proposer(self, cfg: EngineConfig) -> None:
        """Build the pluggable draft proposer (the engine/spec_decode.py
        seam): prompt-lookup (host n-gram scans — the exact PR 3 path),
        the resident draft model, or the combined lookup-then-draft
        proposer. A family without a verify walk gets none."""
        self._draft = None
        self._spec_proposer = None
        if not self._spec_available:
            if cfg.spec_decode_enable == "on":
                logger.warning(
                    "spec_decode_enable='on' needs a verify program, "
                    "which the %s family has none of; speculative "
                    "decoding is disabled.", self._family.name,
                )
            return
        if cfg.spec_proposer == "lookup":
            self._spec_proposer = spec_decode_mod.LookupProposer(
                self._spec_ngram
            )
            return
        self._draft = self._build_draft_runtime(cfg)
        if cfg.spec_proposer == "draft_model":
            self._spec_proposer = spec_decode_mod.DraftModelProposer(
                self._draft
            )
        else:
            self._spec_proposer = spec_decode_mod.CombinedProposer(
                self._spec_ngram, self._draft
            )

    def _resolve_paged_kernel(self, cfg: EngineConfig, model_cfg) -> None:
        """Pick the paged attention server per executable family.

        ``self._paged_kernel`` (block decode, single-query rows),
        ``self._paged_verify_kernel`` (spec verify, K+1-wide rows) and
        ``self._paged_extend_kernel`` (the narrow rungs of chunked
        prefill, folded under the kernel's row cap) each
        hold None (XLA dequant gather) or 'compiled'/'interpret' (the
        ragged Pallas kernel, ops/page_attention.py). The fallback is
        LOUD by contract: an eligible platform whose geometry the
        kernel refuses logs a warning and flags the flight/metric
        stream; per-dispatch accounting rides
        ``genai_engine_paged_attn_dispatches_total{path=...}``.
        """
        import jax

        from generativeaiexamples_tpu.ops import latent_attention, page_attention

        mode = cfg.paged_kernel
        if mode == "off":
            logger.info(
                "paged attention kernel disabled (paged_kernel='off'); "
                "the XLA dequant gather serves all paged dispatches"
            )
            return
        interpret = mode == "interpret"
        # Eligible platforms: a single TPU device, or a pure-TP mesh
        # whose head tiles the shard_map variant serves
        # (parallel/tp_kernels.paged_attention_tp — the geometry probe
        # below checks the LOCAL per-device tile via shards=). Data/
        # hybrid meshes and CPU containers (outside interpret mode) are
        # served correctly by the gather.
        shards = self._tp.shards if self._tp is not None else 1
        single_dev = jax.device_count() == 1 and self._tp is None
        if not interpret and not (
            jax.default_backend() == "tpu"
            and (single_dev or self._tp is not None)
        ):
            # Not a geometry failure — this is informational, not a
            # warning.
            logger.info(
                "paged attention kernel unavailable (backend=%s, "
                "devices=%d, tp=%s); the XLA dequant gather serves all "
                "paged dispatches",
                jax.default_backend(), jax.device_count(),
                self._tp is not None,
            )
            return
        if not interpret and not single_dev and self._tp is None:
            # Multi-device without the TP kernel context (hybrid mesh,
            # or GENAI_TPU_TP_KERNELS=off): no shard_map wrapper to
            # carry the kernel, keep the gather. Interpret mode is
            # exempt — CPU test platforms force a virtual multi-device
            # world while the tp=1 engine still dispatches on one.
            logger.info(
                "paged attention kernel unavailable on a %d-device mesh "
                "without the TP kernel path; the XLA dequant gather "
                "serves all paged dispatches", jax.device_count(),
            )
            return
        kind = "interpret" if interpret else "compiled"
        kv_shape = self._kv_shape  # the pools' own geometry (models/registry.py)
        geom = (
            cfg.page_size, kv_shape.head_dim, kv_shape.num_heads,
            kv_shape.num_kv_heads,
        )
        kv_dtype = cfg.kv_cache_dtype if self._kv_quant else "bfloat16"
        if page_attention.supports_geometry(
            *geom, 1, interpret=interpret, kv_dtype=kv_dtype,
            shards=shards,
        ):
            self._paged_kernel = kind
            # pages of a row one grid step of the read carries: the
            # kernel's own rule over the pool's geometry. A latent pool
            # is read by ops/latent_attention.py, whose decode walks
            # take the pages a step that ITS rule names (rows of
            # head_dim columns in the engine's dtype).
            if kv_shape.latent:
                self._kv_pages_a_step = latent_attention.latent_pages_per_step(
                    cfg.page_size, kv_shape.head_dim, cfg.dtype,
                    self._max_pages_per_slot,
                )
            else:
                self._kv_score_rows = page_attention.score_rows(
                    kv_shape.num_heads, kv_shape.num_kv_heads
                )
                self._kv_pages_a_step = page_attention.pool_pages_per_step(
                    cfg.page_size, kv_shape.num_kv_heads, kv_shape.head_dim,
                    ("uint8" if self._kv_packed else "int8")
                    if self._kv_quant else cfg.dtype,
                    scale_plane=self._kv_scale_plane,
                )
            logger.info(
                "ragged page-attention kernel serving paged decode "
                "(%s, page_size=%d%s)", kind, cfg.page_size,
                f", {shards}-way shard_map" if shards > 1 else "",
            )
        else:
            logger.warning(
                "ragged page-attention kernel REFUSED this geometry "
                "(page_size=%d head_dim=%d heads=%d kv_heads=%d "
                "kv_dtype=%s shards=%d) — paged decode falls back to "
                "the XLA dequant gather; every dispatch is charged to "
                "genai_engine_paged_attn_dispatches_total{path='gather'}",
                *geom, kv_dtype, shards,
            )
            flight_recorder.event(
                "paged_kernel_fallback", reason="geometry",
                page_size=cfg.page_size, head_dim=kv_shape.head_dim,
                heads=kv_shape.num_heads, kv_heads=kv_shape.num_kv_heads,
                kv_dtype=kv_dtype, shards=shards,
            )
            return
        verify_rows = spec_decode_mod.effective_draft_len(cfg) + 1
        if page_attention.supports_geometry(
            *geom, verify_rows, interpret=interpret, kv_dtype=kv_dtype,
            shards=shards,
        ):
            self._paged_verify_kernel = kind
        else:
            logger.info(
                "spec-verify chunks (%d query rows x %d heads) exceed "
                "the page kernel's row cap; verify dispatches stay on "
                "the XLA gather", verify_rows, kv_shape.num_heads,
            )
        # A prompt's tail (the width ladder's rungs under prefill_chunk,
        # or a packed axis shorter than a chunk) reads through the
        # kernel when every such width folds into sub-rows the kernel
        # serves: ONE executable a row rung, no window rung (the walk
        # follows each row's live pages).
        narrow = (
            [t for t in self.shapes.packed_rungs() if t < cfg.prefill_chunk]
            if self.shapes.packed else self.shapes.chunk_widths()[:-1]
        )
        if narrow and all(
            page_attention.supports_geometry(
                *geom,
                page_attention.query_fold(w, kv_shape.num_heads // shards),
                interpret=interpret, kv_dtype=kv_dtype, shards=shards,
            )
            for w in narrow
        ):
            self._paged_extend_kernel = kind

    def _init_scheduler_state(self, cfg: EngineConfig) -> None:
        """Slot bookkeeping + dispatch/reader threads."""
        import jax
        import jax.numpy as jnp

        from generativeaiexamples_tpu.parallel.mesh import mesh_context

        # Per-slot prompt+output token buffers the host proposer matches
        # against (dispatch-thread-owned; populated at admission, extended
        # after each synced verify dispatch, dropped at slot release).
        self._spec_ctx: Dict[int, List[int]] = {}  # guarded by self._lock
        # Pipelined spec dispatch (spec_pipeline_enable, resolved ONCE
        # like _dtl/_annotate: 'off' pins the flag and every spec round
        # takes the exact synchronous prior path). All three fields are
        # dispatch-thread-owned:
        #   _spec_pending   in-flight verify (packed handle + the host
        #                   state needed to land it one round late)
        #   _spec_reconcile (confirmed, missed) runahead drafts from the
        #                   last flush, consumed by the next spec round
        #   _spec_stage     double-buffered host staging arrays for the
        #                   verify inputs (generation N+1 fills one
        #                   buffer while generation N's may still back
        #                   an in-flight transfer)
        self._spec_pipeline = (
            getattr(cfg, "spec_pipeline_enable", "on") != "off"
        )
        self._spec_pending: Optional[dict] = None
        self._spec_reconcile: Optional[tuple] = None
        self._spec_stage: Optional[tuple] = None
        # Page-table scatter staging (per tier thread — see
        # _table_stage_arrays).
        self._table_stage: Dict[str, tuple] = {}
        # Decode chains on-device: token/position/sampling state lives in
        # device arrays that feed each step's output into the next step's
        # input with NO host round-trip. A separate reader thread drains
        # results (the only host syncs), bounded by decode_runahead — the
        # decode thread must never wait for the host. (The default depth
        # was tuned against a slow readback that no longer exists;
        # re-measurement is queued in ROADMAP.md.)
        import collections

        self._free_slots = list(range(self.num_slots))  # guarded by self._lock
        self._slot_req: Dict[int, _Request] = {}  # guarded by self._lock
        # Open text streams by id(req), from a stream's first wake-up to
        # its close: what stream_backlog_tokens sums over. A stream
        # outlives its slot while its consumer still drains. Single dict
        # operations only, so no lock.
        self._streams: Dict[int, _Request] = {}
        # FIFO admission queue (a deque lets unadmitted requests stay at
        # the FRONT across one-wave admission rounds).
        self._pending: "collections.deque[_Request]" = collections.deque()  # guarded by self._lock
        # Decode steps left before each slot's request exhausts max_tokens —
        # maintained on the dispatch thread so budget-exhausted slots free
        # EAGERLY (host arithmetic, no readback round-trip): without this,
        # every request burns decode_runahead * decode_block extra steps
        # after its last token while the release crawls back via the reader.
        self._slot_budget: Dict[int, int] = {}  # guarded by self._lock
        # Host-side shadow of each live slot's decode position (advanced by
        # decode_block per dispatch) — drives the attention-window bucket.
        self._slot_pos: Dict[int, int] = {}  # guarded by self._lock
        with mesh_context(self._mesh):
            # The slot arrays start as what every later version of them
            # is: OUTPUTS of a program over the engine's weights
            # (update_slots, decode), committed and with the mesh in
            # their type. jit keys an executable on both, so plain
            # jnp.zeros here made the first admissions and the first
            # decode block select other executables of update_slots and
            # decode than the steady state's: loads on the hot path that
            # only jit's own events show (compile_watch.py; _zero_hidden
            # is the same cure for the extend carries).
            def slot_state(embed):
                zero = jnp.where(False, embed[0, 0], 0)  # reads a weight, keeps none

                def full(dtype, fill):
                    return jnp.full(self.num_slots, fill, dtype) + zero.astype(dtype)

                return (full(jnp.int32, 0), full(jnp.int32, 0),
                        full(jnp.float32, 1.0), full(jnp.float32, 1.0),
                        full(jnp.int32, 0))

            (
                self._tokens_dev,
                self._positions_dev,
                self._temps_dev,
                self._topps_dev,
                self._seeds_dev,
            ) = jax.jit(slot_state)(self.params["embed"])
            # Per-slot page tables, device-resident: row b lists the
            # physical pool pages backing slot b's sequence, scratch
            # (page 0) padded. Rewritten per admission wave by ONE
            # scatter; every dispatch reads it as a plain operand.
            self._tables_dev = jnp.zeros(
                (self.num_slots, self._max_pages_per_slot), jnp.int32
            )
            self._tables_fn = self._compile_watch.wrap(
                "page_tables",
                jax.jit(lambda t, slots, rows: t.at[slots].set(rows)),
            )
            # slot -> page list (written by the dispatch thread; the
            # request's full reservation, shared prefix pages first —
            # paged_stats() iterates it from scraper threads).
            self._slot_pages: Dict[int, List[int]] = {}  # guarded by self._lock
        self._step_count = 0
        # warmup(): hold admissions to force wave shape
        self._paused = False  # guarded by self._lock
        self._lock = threading.Condition()
        # Drain state machine (docs/resilience.md, "Preemption and
        # drain lifecycle"): _draining refuses new submits and tells
        # the dispatch loop to park at its next block boundary;
        # _drain_parked is the loop's acknowledgement — the drain
        # thread waits for it (plus a quiesced prefill tier) before it
        # touches live request state.
        self._draining = False  # guarded by self._lock
        self._drain_parked = False  # guarded by self._lock
        # Snapshot restores execute ON the dispatch thread (_loop
        # drains this queue before admission): every decode dispatch
        # zeroes dead slots' position rows, so a restored slot's device
        # writes and its batch registration must be atomic w.r.t.
        # decode dispatch enqueues — only the dispatch thread can
        # guarantee that without nesting the engine and dispatch locks.
        self._restore_q: "queue.Queue[tuple]" = queue.Queue()
        # Bounded on-disk spool for preempted-request snapshots,
        # stamped with this engine's config fingerprint: restore on a
        # differently-configured engine is REFUSED, not garbled.
        self._spool = request_snapshot_mod.SnapshotSpool(
            cfg.snapshot_spool_dir,
            cfg.snapshot_spool_max,
            fingerprint=provenance_mod.config_fingerprint(cfg),
        )
        # Serializes every compiled-program call that consumes shared
        # DONATED device state (KV pool/caches, slot state arrays)
        # together with its output rebind: under the disagg scheduler
        # policy the prefill tier and the decode tier dispatch from two
        # threads, and two concurrent consumers of the same donated
        # buffer version is a use-after-free. Held only across the
        # async enqueue + rebind — never across device execution — so
        # prefill chunks and decode blocks still interleave on the
        # device stream. Uncontended (single dispatch thread) under the
        # unified policy. RLock: warmup paths nest dispatch sections.
        self._dispatch_lock = threading.RLock()
        self._running = True  # guarded by self._lock
        self._release_q: "queue.Queue[Tuple[int, _Request]]" = queue.Queue()
        self._readback: "queue.Queue[Optional[tuple]]" = queue.Queue(
            maxsize=max(1, cfg.decode_runahead)
        )
        _M_SLOTS_CAPACITY.set(self.num_slots)
        _M_SLOTS_IN_USE.set(0)
        # ENABLE_PROFILING resolves ONCE here: off -> nullcontext factory,
        # zero cost in the dispatch loop; on -> jax.profiler.TraceAnnotation
        # labels every prefill-wave / decode-block dispatch in captures.
        self._annotate = profiling.annotation_scope()
        # Dispatch timeline (engine/dispatch_timeline.py): resolved ONCE
        # like _annotate — GENAI_DISPATCH_TIMELINE=off pins _dtl to None
        # and every capture site collapses to its exact prior path.
        self._dtl = (
            dispatch_timeline_mod
            if dispatch_timeline_mod.enabled() else None
        )
        # the one thread that awaits each launch's output and stamps its
        # span's completion (no-op when the timeline is off)
        dispatch_timeline_mod.start_watcher()
        self._stop_ids = set(self.tokenizer.stop_ids())
        # Dispatch-loop watchdog state: _last_progress advances whenever
        # the loop completes a wait or an iteration; a hang INSIDE the
        # try block (wedged dispatch, stuck device call) leaves it stale
        # while work is outstanding, which is the wedge signal.
        self._last_progress = time.time()  # guarded by self._lock
        self._wedged = False
        # Per-element KV cache width (float: int4 packs two values per
        # byte — utils/hardware owns the map).
        self._kv_byte_width = (
            hardware.kv_bytes_per_element(cfg.kv_cache_dtype)
            if self._kv_quant else 2
        )
        # A replacement engine starts healthy: the module-global wedge
        # signal may still be set by a prior instance (watchdog or failed
        # shutdown join), and _clear_wedged's `if self._wedged` guard
        # would never clear it on this instance's behalf — readiness
        # would report 503 forever while the rebuilt engine serves fine.
        ENGINE_WEDGED.clear()
        _M_WEDGED.set(0)
        # The pluggable scheduler policy (engine/scheduler/,
        # docs/scheduler.md): admission, wave formation, and slot
        # placement live behind this seam. 'unified' (default)
        # reproduces the exact monolithic dispatch order; 'disagg'
        # spawns the prefill tier worker in start() below.
        self.scheduler = scheduler_mod.build_policy(cfg, self)
        self._wd_stop = threading.Event()
        self._thread = threading.Thread(target=self._loop, daemon=True, name="llm-decode")
        self._reader = threading.Thread(target=self._reader_loop, daemon=True, name="llm-reader")
        self._thread.start()
        self._reader.start()
        self.scheduler.start()
        self._watchdog = None
        if cfg.watchdog_stall_s > 0:
            self._watchdog = threading.Thread(
                target=self._watchdog_loop, daemon=True, name="llm-watchdog"
            )
            self._watchdog.start()

    def _init_prefix_cache(self, cfg: EngineConfig) -> None:
        """Automatic prefix KV-cache reuse (radix cache,
        engine/prefix_cache.py, docs/prefix_cache.md).

        Zero-copy: entries hold refcounted POOL pages (no separate
        store buffers, no compiled copy programs). A request whose
        prompt starts with a cached chunk-aligned prefix gets the
        shared pages mapped into its page table, and chunked prefill
        runs only over the uncached suffix — the fixed-shape chunk
        dispatches and the wave-padding ladder stay exactly as they
        are; the post-prefill insert donates the request's own prompt
        pages the same way. The drop hook returns an evicted entry's
        pages to the allocator. store-slot ids remain as entry-count
        tickets bounding the index at prefix_cache_slots entries.
        """
        self._prefix = None
        # whether a row's pages or an entry's changed since the gauge of
        # shared pages was last computed (_update_occupancy_gauges)
        self._shared_pages_stale = False
        if cfg.prefix_cache_enable == "off" or cfg.prefix_cache_slots <= 0:
            return
        P = cfg.prefix_cache_slots
        self._prefix = prefix_cache_mod.PrefixCache(
            chunk=cfg.prefill_chunk, slots=P, max_len=self.max_seq_len,
            on_drop=self._drop_prefix_pages,
            stateful=self._state_store_rows > 0,
        )
        logger.info(
            "prefix KV cache enabled (zero-copy): %d entries over the "
            "shared page pool (chunk %d)%s",
            P, cfg.prefill_chunk,
            "; each carries a fixed-state row behind the slots'"
            if self._state_store_rows else "",
        )

    def _drop_prefix_pages(self, entry) -> None:
        """Prefix-cache drop hook (paged layout): an entry leaving the
        radix index releases its refcounted pool pages. Runs under the
        cache lock; the allocator has its own (never calls back)."""
        pages = getattr(entry, "pages", None)
        if pages and self._kv_alloc is not None:
            self._kv_alloc.release(pages)
        entry.pages = None
        self._shared_pages_stale = True

    def paged_stats(self) -> Dict[str, float]:
        """Page-pool view (tests, operators): allocator occupancy plus
        live-request token accounting."""
        stats = self._kv_alloc.stats()
        page = self.engine_config.page_size
        with self._lock:
            held = sum(len(p) for p in self._slot_pages.values())
            live = sum(
                min(p, self.max_seq_len) for p in self._slot_pos.values()
            )
        stats["request_pages_held"] = held
        stats["live_tokens"] = live
        alloc_tokens = held * page
        stats["fragmentation"] = (
            1.0 - live / alloc_tokens if alloc_tokens else 0.0
        )
        # mean/peak live-page basis (kv_pages.PageAllocator.occupancy)
        # already rides stats(); name the serving path next to it so
        # one snapshot answers "which attention server, at what
        # occupancy".
        stats["attn_path"] = "kernel" if self._paged_kernel else "gather"
        return stats

    def _fund_paged_admissions(self, admitted: List[_Request]) -> List[_Request]:
        """Reserve every page each admitted request can touch — prompt +
        generation budget + dispatch slack, minus the prefix pages a
        radix hit maps zero-copy (refcount bump, no device work). Runs
        on the dispatch thread between slot claim and the first prefill
        dispatch. A request the pool cannot fund (after LRU-evicting
        unpinned prefix entries) returns its slot and goes back to the
        queue FRONT with every later claim, preserving FIFO order —
        that is the OOM backpressure the allocator tests pin: the pool
        can never over-commit, so no dispatch ever allocates. Ends by
        scattering the funded rows' page tables to the device."""
        import jax.numpy as jnp

        page = self.engine_config.page_size
        chunk = self.engine_config.prefill_chunk
        funded: List[_Request] = []
        rows: List[List[int]] = []  # funded requests' page lists
        for idx, req in enumerate(admitted):
            ent = req.prefix_entry
            shared: List[int] = []
            if ent is not None:
                shared = list(getattr(ent, "pages", None) or ())
                shared = shared[: req.prefix_len // page]
                if len(shared) * page < req.prefix_len:
                    # Entry carries fewer pages than the matched depth
                    # (defensive — insert donates the full span): shrink
                    # the cached skip to the page-backed, chunk-aligned
                    # prefix so no skipped chunk reads unbacked rows.
                    req.prefix_len = (len(shared) * page // chunk) * chunk
                    shared = shared[: req.prefix_len // page]
                # Retain FIRST, then unpin: in the paged layout the
                # allocator refcount (not the entry pin) is what keeps
                # shared pages alive, and holding the pin through the
                # evict-and-retry loop below would block evicting THIS
                # entry — a funding livelock on a minimal pool where
                # the request's own pinned match holds the very pages
                # whose eviction would fund it.
                if shared:
                    self._kv_alloc.retain(shared)
                self._prefix.release(ent)
                req.prefix_entry = None
                if self._state_store_rows:
                    req.prefix_via = ent
            total = kv_pages_mod.pages_needed(
                len(req.prompt_ids), req.params.max_tokens, page,
                self.max_seq_len, self._page_slack,
            )
            fresh_n = max(0, total - len(shared))
            fresh = self._kv_alloc.alloc(fresh_n, count_failure=False)
            while (
                fresh is None
                and self._prefix is not None
                and self._prefix.evict_lru()
            ):
                fresh = self._kv_alloc.alloc(fresh_n, count_failure=False)
            if fresh is None:
                # only the final give-up is a backpressure event — the
                # evict-and-retry attempts above are healthy churn
                kv_pages_mod.record_alloc_failure()
                if shared:
                    self._kv_alloc.release(shared)  # undo the map
                # Requeue this and every later claim (front, original
                # order); the pool refills as live requests release.
                with self._lock:
                    for r in reversed(admitted[idx:]):
                        if r.prefix_entry is not None and self._prefix is not None:
                            self._prefix.release(r.prefix_entry)
                            r.prefix_entry = None
                        r.prefix_len = 0
                        r.prefix_via = None
                        self._free_slots.append(r.slot)
                        r.slot = -1
                        self._pending.appendleft(r)
                    _M_QUEUE_DEPTH.set(len(self._pending))
                    stalled = not funded and not self._slot_req
                flight_recorder.event_rid(
                    req.rid, "page_backpressure", pages_short=fresh_n,
                )
                if stalled:
                    # Nothing live to free pages and nothing admitted:
                    # bound the dispatch loop's retry spin while shared
                    # refcounts drain (prefix-held pages of in-flight
                    # fetches, a closing wave's releases).
                    time.sleep(0.002)
                break
            if shared:
                kv_pages_mod.record_prefix_mapped(len(shared))
                flight_recorder.event_rid(
                    req.rid, "prefix_pages_mapped",
                    pages=len(shared), tokens=req.prefix_len,
                )
            pages = shared + fresh
            with self._lock:
                # paged_stats() iterates this dict under the lock from
                # scraper threads; an unlocked insert here can blow up
                # their .values() walk mid-iteration
                self._slot_pages[req.slot] = pages
                self._shared_pages_stale = True
            flight_recorder.event_rid(
                req.rid, "page_alloc", fresh=len(fresh), shared=len(shared),
                # which attention server this request's decode dispatches
                # run through — timelines answer "kernel or gather?"
                # per request, not just in aggregate
                attn_path="kernel" if self._paged_kernel else "gather",
            )
            funded.append(req)
            rows.append(pages)
        if funded:
            # Pre-staged scatter args, double-buffered per tier thread
            # (the prefill tier funds waves under disagg; the dispatch
            # thread under unified): the fills and the host→device
            # copies run OUTSIDE the dispatch lock while the device
            # chews earlier work, so the lock covers only the scatter
            # enqueue + table rebind.
            slots_h, rows_h = self._table_stage_arrays(len(funded))
            for i, (r, pages) in enumerate(zip(funded, rows)):
                slots_h[i] = r.slot
                rows_h[i, : len(pages)] = pages
            slots_dev = jnp.asarray(slots_h)
            rows_dev = jnp.asarray(rows_h)
            # Dispatch lock: the table array is rebound here and read
            # as an operand by the decode tier's dispatches; under
            # disagg the two run on different threads.
            with self._dispatch_lock:
                # genai-lint: disable=shape-cardinality -- scatter rows are deliberately UNPADDED (warmup walks every count 1..num_slots, so all |funded| shapes are pre-compiled)
                self._tables_dev = self._tables_fn(
                    self._tables_dev, slots_dev, rows_dev
                )
        return funded

    def _table_stage_arrays(self, n: int):
        """Pre-staged host arrays for the page-table scatter args,
        double-buffered per tier thread: wave N+1 fills one buffer
        while wave N's may still back an in-flight host→device copy.
        Returns length-n views so the scatter keeps hitting the warmed
        per-row-count executables."""
        name = threading.current_thread().name
        stage = self._table_stage.get(name)
        if stage is None:
            stage = self._table_stage[name] = (
                [
                    (
                        np.zeros((self.num_slots,), np.int32),
                        np.zeros(
                            (self.num_slots, self._max_pages_per_slot),
                            np.int32,
                        ),
                    )
                    for _ in range(2)
                ],
                [0],
            )
        bufs, idx = stage
        slots_h, rows_h = bufs[idx[0]]
        idx[0] = 1 - idx[0]
        slots_view = slots_h[:n]
        rows_view = rows_h[:n]
        rows_view[:] = 0  # unused tail entries pad to the scratch page
        return slots_view, rows_view

    def _per_device_hbm(self) -> float:
        """One rule for per-device HBM (utils/hardware.device_hbm_bytes:
        the allocator's limit on a TPU — missing is an error there — the
        reference part's size on other backends, GENAI_TPU_HBM_BYTES
        overriding both). Shared by the fit planner and every budget
        warning so they can't disagree."""
        import jax

        return hardware.device_hbm_bytes(jax.devices()[0])

    def device_memory_line(self) -> str:
        """Allocator view of every mesh device, for start-up log lines
        (``peak`` is the high-water mark since process start). Backends
        without ``memory_stats()`` (CPU) say so instead of a number."""
        parts = []
        for dev in self._mesh.devices.reshape(-1):
            stats = dev.memory_stats()
            if not stats:
                return f"device memory: not reported on {dev.platform}"
            parts.append(
                f"dev{dev.id} in_use={stats['bytes_in_use'] / 1e9:.2f}GB "
                f"peak={stats['peak_bytes_in_use'] / 1e9:.2f}GB "
                f"limit={stats['bytes_limit'] / 1e9:.2f}GB"
            )
        return "device memory: " + "; ".join(parts)

    def _check_memory_budget(self, cfg: EngineConfig, model_cfg) -> None:
        """Fit-plan the weights + KV cache against aggregate device HBM.

        The 70B-class capacity contract (BASELINE.md; reference requires
        320 GB of GPU memory for 70B inference, docs/support-matrix.md:
        43-46): int8 llama3-70b ≈ 69 GB of weights, so a v5e-8 slice
        (8 x 16 GB) fits it ONLY with TP over the full model axis plus an
        int8 KV cache. A config that cannot fit logs a clear budget line
        instead of dying later in a fragmented device OOM.
        """
        serving_memory_bytes = self._family.serving_memory_bytes

        wbytes = 1 if cfg.quantization in ("int8", "w8a8") else 2
        kvbytes = hardware.kv_bytes_per_element(cfg.kv_cache_dtype)
        # The prefix-cache store is extra rows-of-cache: account for it
        # as additional batch slots (the auto-layout gate isn't resolved
        # yet, so this can only over-estimate).
        extra_slots = _prefix_store_extra_slots(cfg)
        rows = cfg.max_batch_size + extra_slots
        seq = min(cfg.max_seq_len, model_cfg.max_seq_len)
        if cfg.kv_pool_pages > 0:
            # an explicit pool is what is paged, whatever rows x capacity
            # would come to (a fixed state is still counted a row)
            seq = min(seq, -(-cfg.kv_pool_pages * cfg.page_size // rows))
        est = serving_memory_bytes(
            model_cfg,
            rows,
            seq,
            weight_bytes=wbytes,
            kv_bytes=kvbytes,
        )
        if cfg.spec_decode_enable == "on":
            # The verify dispatch widens decode activations from 1 to
            # K+1 tokens per row; the dominant term is the
            # [B*(K+1), V] f32 logits plus the chunk hidden states.
            # Counted here so a config that fits plain decode but not
            # the verify width warns at startup, not in a device OOM.
            spec_k = spec_decode_mod.effective_draft_len(cfg)
            spec_bytes = (
                4.0 * cfg.max_batch_size * (spec_k + 1)
                * (model_cfg.vocab_size + 2 * model_cfg.hidden_size)
            )
            est["total"] += spec_bytes
            logger.info(
                "spec-decode verify activations: +%.2f GB (K=%d)",
                spec_bytes / 1e9, spec_k,
            )
        if cfg.spec_proposer in ("draft_model", "combined"):
            # Resident draft model: its dense weights plus a full
            # private KV cache (one dense strip per decode slot) sit in
            # HBM next to the target — the fit plan must see them or a
            # config that fits the target alone OOMs the moment the
            # draft builds (engine/spec_draft.py). NOT gated on
            # spec_decode_enable: _init_spec_proposer builds the
            # runtime whenever a draft proposer is configured (so a
            # runtime set_spec_decode(True) toggle finds it resident),
            # and resident HBM must be budgeted resident.
            from generativeaiexamples_tpu.engine import spec_draft as spec_draft_mod

            try:
                draft_cfg = spec_draft_mod.resolve_draft_config(cfg)
            except ValueError:
                draft_cfg = None  # engine init re-raises with context
            if draft_cfg is not None:
                draft_est = serving_memory_bytes(
                    draft_cfg,
                    cfg.max_batch_size,
                    min(cfg.max_seq_len, draft_cfg.max_seq_len),
                    weight_bytes=2,  # draft weights stay dense bf16
                    kv_bytes=1 if cfg.spec_draft_kv_dtype == "int8" else 2,
                )
                est["total"] += draft_est["total"]
                logger.info(
                    "resident draft model: +%.2f GB weights, +%.2f GB "
                    "KV (spec_proposer=%s)",
                    draft_est["weights"] / 1e9,
                    draft_est["kv_cache"] / 1e9,
                    cfg.spec_proposer,
                )
        per_dev_hbm = self._per_device_hbm()
        budget = per_dev_hbm * self._mesh.size * 0.92  # working-set headroom
        logger.info(
            "serving memory estimate: weights=%.1f GB + kv=%.1f GB over "
            "%d device(s) (%.1f GB HBM aggregate)",
            est["weights"] / 1e9,
            est["kv_cache"] / 1e9,
            self._mesh.size,
            per_dev_hbm * self._mesh.size / 1e9,
        )
        if est["total"] > budget:
            hint = ""
            if wbytes > 1:
                hint = " Enable quantization=int8 (halves weight bytes)."
            elif kvbytes > 1:
                hint = " Enable kv_cache_dtype=int8 (halves cache bytes)."
            elif self._mesh.size == 1:
                hint = " Shard over more devices (tensor_parallelism)."
            logger.warning(
                "Estimated serving memory %.1f GB exceeds ~%.1f GB usable "
                "HBM on this %d-device mesh — expect OOM.%s",
                est["total"] / 1e9,
                budget / 1e9,
                self._mesh.size,
                hint,
            )

    def _resolve_parallelism(self, cfg: EngineConfig, model_cfg) -> int:
        """The model-axis width for mesh construction. An explicit
        ``tensor_parallelism`` wins. With the default (-1, every local
        device) an architecture whose head / MLP / vocab / hidden sizes
        cap the model axis below the device count gets that cap (spare
        devices idle) instead of an indivisible model axis that fails
        at cache sharding; what then does not fit is
        _check_memory_budget's to say."""
        import math

        import jax

        tp = cfg.tensor_parallelism
        n = len(jax.devices())
        if tp != -1 or n <= 1:
            return tp
        tp_cap = math.gcd(
            math.gcd(
                math.gcd(model_cfg.num_heads, model_cfg.num_kv_heads),
                math.gcd(
                    model_cfg.intermediate_size,
                    math.gcd(model_cfg.vocab_size, model_cfg.hidden_size),
                ),
            ),
            n,
        )
        if tp_cap >= n or n % tp_cap:
            return tp
        return tp_cap

    def _build_steps(self) -> None:
        """The compiled step programs, each built once: extend (a
        prefill chunk), finish, decode block and speculative verify over
        per-layer weights and the page pool (docs/paged_kv.md)."""
        import jax
        import jax.numpy as jnp

        from generativeaiexamples_tpu.models.sampling import sample_keys, sample_tokens

        cfg = self.model_config
        ecfg = self.engine_config
        V = self._sample_vocab
        quant_kernel = self._quant_kernel
        tp = self._tp
        base_key = jax.random.PRNGKey(1234)
        max_pos = self.max_seq_len - 1
        block = self.shapes.decode_block

        # The step programs reach the model through its family alone
        # (models/registry.py): walks over an opaque cache pytree
        # — page pools and, for a fixed-state family, per-slot arrays
        # the walks index by slot (reset at admission inside the first
        # extend program, carried from chunk to chunk, untouched
        # by a dead decode row) — with cache coordinates routed through
        # the per-slot page tables (one [B, Pmax] int32 operand). The
        # ragged Pallas kernel (resolved per program by
        # _resolve_paged_kernel) replaces the gather READ where geometry
        # allows; writes are identical either way.
        fam = self._family
        # the kernel paths this engine resolved; a family takes what it knows
        paths = dict(quant_kernel=quant_kernel, tp=tp, **self._family_kernels)
        # a family's small counts of its last walk, handed back with the
        # tokens (models/registry.py ``stat_names``); none: nothing added
        n_stats = len(self._stat_names)
        page = ecfg.page_size
        page_kernel = self._paged_kernel
        verify_kernel = self._paged_verify_kernel

        def decode_paged(params, caches, tokens, positions, temps, topps,
                         seeds, tables, live, window):
            # `block` steps for the whole batch in ONE dispatch, feeding
            # themselves (lax.scan): the host gets ONE [block, batch]
            # slab back, one readback and one launch per `block` tokens.
            # `live` zeroes dead slots' positions so the page kernel's
            # per-row walks don't track stale lengths.
            positions = jnp.where(live, positions, 0)

            def body(carry, _):
                tokens, positions, caches = carry
                logits, caches = fam.decode_paged(
                    params, cfg, caches, tokens, positions, live, tables,
                    window, page, page_kernel=page_kernel, **paths,
                )
                keys = sample_keys(
                    base_key, seeds, jnp.minimum(positions + 1, max_pos)
                )
                # a dead slot keeps its last request's temperature and
                # top_p (1.0, 1.0 if never used): only live rows decide
                # what the sampler reads the vocabulary for
                next_tokens = sample_tokens(
                    logits[:, :V], keys, temps, topps, live
                )
                positions = jnp.minimum(positions + 1, max_pos)
                return (next_tokens, positions, caches), next_tokens

            (tokens, positions, caches), token_slab = jax.lax.scan(
                body, (tokens, positions, caches), None, length=block
            )
            if n_stats:
                # the last step's counts ride under the token slab as
                # extra rows: one readback, no sync of their own
                B = token_slab.shape[1]
                stats = jnp.pad(
                    fam.read_stats(caches).astype(token_slab.dtype),
                    (0, -n_stats % B),
                )
                token_slab = jnp.concatenate(
                    [token_slab, stats.reshape(-1, B)], axis=0
                )
            return tokens, positions, caches, token_slab

        # Chunked prefill (VERDICT r3 #4): every prompt runs as
        # repeated (rows, width, W)-shaped extend dispatches — a
        # BOUNDED executable set (shapes.extend_signatures: row rungs x window
        # rungs at the full chunk width, row rungs alone at a narrow
        # one) covering every prompt length, so no request can hit a
        # cold-bucket compile (observed without it: p95 108 s on
        # developer_rag e2e when retrieval crossed cold buckets, and 36
        # single-bucket waves for 48 mixed-length questions).
        chunk = ecfg.prefill_chunk
        extend_kernel = self._paged_extend_kernel

        def extend_batch_paged(params, caches, tokens, offsets, valid,
                               slots, last_h, tables, window):
            # a narrow width (a prompt's tail) reads through the page
            # kernel where it resolved; a family without that read
            # gathers the window it is given
            narrow = tokens.shape[1] < chunk
            cand, caches = fam.extend_paged(
                params, cfg, caches, tokens, offsets, valid, slots, tables,
                window, page,
                page_kernel=extend_kernel if narrow else None, **paths,
            )
            # (a family may hand back a wider hidden state than the
            # carried one; the carry keeps ONE dtype, so ONE executable)
            last_h = jnp.where(
                (valid > 0)[:, None], cand.astype(last_h.dtype), last_h
            )
            if n_stats:
                return last_h, caches, fam.read_stats(caches)
            return last_h, caches

        def place_rows(last_h, dest, sub_h):
            # Row ``dest[j]`` of the wave's carry takes ``sub_h[j]``; a
            # ``dest`` out of range names no row. A GATHER, not
            # ``last_h.at[dest].set(sub_h, mode="drop")``: on the chip
            # that scatter, given rows to drop, wrote ONE live row's
            # update over the others' (two rows of a wave then sampled
            # their first token from the same hidden state; PERF.md
            # section 6, PR 41; the CPU does not show it).
            hit = dest[None, :] == jnp.arange(last_h.shape[0])[:, None]
            return jnp.where(
                hit.any(axis=1)[:, None],
                sub_h[jnp.argmax(hit, axis=1)].astype(last_h.dtype), last_h,
            )

        packed = self.shapes.packed
        packed_windows = tuple(self.shapes.packed_windows()) if packed else ()

        def extend_packed(params, caches, tokens, rows, pick, last_h, tables):
            # The packed form of the same dispatch: the wave's live
            # tokens on ONE axis [T], row after row. ``rows`` [5, R]:
            # each row's start on the axis, live tokens, first cache
            # position, slot and place in the wave's carry; ``pick``:
            # live rows and the index of the chunk's window rung. A T
            # under a chunk reads through the page kernel where it
            # resolved; the gather picks its window inside the program.
            T, R = tokens.shape[0], rows.shape[1]
            starts, counts, offsets, slots, dest = rows
            cand, caches = fam.extend_packed(
                params, cfg, caches, tokens, starts, counts, offsets, slots,
                tables, page, seg=min(T, chunk), windows=packed_windows,
                window_index=pick[1], n_rows=pick[0],
                page_kernel=extend_kernel if T < chunk else None, **paths,
            )
            last_h = place_rows(last_h, jnp.where(counts > 0, dest, R), cand)
            if n_stats:
                return last_h, caches, fam.read_stats(caches)
            return last_h, caches

        def finish_batch(params, last_h, src, lengths, temps, topps, seeds):
            # `src` maps each wave row to the row whose hidden it
            # samples: itself, or row 0 for a padding row (a copy of row
            # 0 that no chunk computed, so the duplicate slot scatter of
            # the admission stays one value)
            logits = fam.head(params, cfg, last_h[src], **paths)
            keys = sample_keys(base_key, seeds, lengths)
            return sample_tokens(logits[:, :V], keys, temps, topps)

        def put_rows(last_h, rows, sub_h):
            # a chunk that ran on fewer rows than the wave holds hands
            # its hidden states back (a padding row's index is out of
            # range: dropped)
            return place_rows(last_h, rows, sub_h)

        # Speculative verify step (prompt-lookup decoding, docs/
        # spec_decode.md): score the last accepted token plus K host-
        # drafted tokens for EVERY slot in one dispatch, sample each of
        # the K+1 positions with the same (seed, position) keys plain
        # decode would use, and advance each row past the longest
        # greedy-matching draft prefix plus the bonus token — all on
        # device, so the only host traffic is the [B, K+1] token slab
        # plus the accepted counts. Rows without a draft (no n-gram
        # match, temperature>0, dead slots) run as valid=1 single-token
        # rows inside the same program, which is what keeps greedy and
        # sampled streams token-identical to the non-spec path.
        ecfg = self.engine_config
        K = self._spec_draft = spec_decode_mod.effective_draft_len(ecfg)
        self._spec_ngram = max(1, ecfg.spec_ngram_max)
        # Acceptance-adaptive draft width (spec_adaptive_k=on): each
        # round picks its verify width from a closed halving ladder
        # driven by the scheduler's rolling acceptance window. Funding
        # stays at the configured max K (one-K rule), and warmup walks
        # the whole ladder so every rung is a warmed executable.
        self._adaptive_k = None
        if getattr(ecfg, "spec_adaptive_k", "off") == "on":
            self._adaptive_k = spec_decode_mod.AdaptiveK(
                K,
                k_min=getattr(ecfg, "spec_adaptive_k_min", 1),
                threshold=getattr(ecfg, "spec_adaptive_k_threshold", 0.5),
            )

        def spec_verify_paged(params, caches, tokens, positions, temps,
                              topps, seeds, draft, draft_len, live,
                              tables, window):
            B, Kd = draft.shape
            Kp1 = Kd + 1
            offsets = jnp.where(live, positions, 0)
            chunk = jnp.concatenate([tokens[:, None], draft], axis=1)
            valid = jnp.where(live, 1 + draft_len, 0)
            slot_ids = jnp.arange(B, dtype=jnp.int32)
            logits, caches = fam.verify_paged(
                params, cfg, caches, chunk, offsets, valid, slot_ids, tables,
                window, page, page_kernel=verify_kernel, **paths,
            )  # [B, K+1, V]
            # output token j lands at absolute position offsets + j + 1:
            # identical sampling keys to the plain decode loop, so a row
            # that accepts nothing still emits exactly its normal token
            pos_grid = jnp.minimum(
                offsets[:, None] + 1
                + jnp.arange(Kp1, dtype=jnp.int32)[None, :],
                max_pos,
            )
            keys = sample_keys(
                base_key, jnp.repeat(seeds, Kp1), pos_grid.reshape(-1)
            )
            out_tokens = sample_tokens(
                logits[..., :V].reshape(B * Kp1, V),
                keys,
                jnp.repeat(temps, Kp1),
                jnp.repeat(topps, Kp1),
                jnp.repeat(live, Kp1),
            ).reshape(B, Kp1)
            # accepted = leading draft positions whose token matches the
            # model's own output at the same index (cumprod counts the
            # run of 1s); the bonus token at index `accepted` is the
            # model's continuation after the accepted prefix
            drafted = (
                jnp.arange(Kd, dtype=jnp.int32)[None, :] < draft_len[:, None]
            )
            match = (draft == out_tokens[:, :Kd]) & drafted
            accepted = jnp.sum(
                jnp.cumprod(match.astype(jnp.int32), axis=1), axis=1
            )
            row = jnp.arange(B, dtype=jnp.int32)
            new_tokens = jnp.where(live, out_tokens[row, accepted], tokens)
            new_positions = jnp.where(
                live, jnp.minimum(positions + accepted + 1, max_pos), positions
            )
            # One packed [B, K+2] host-facing result (tokens ‖ accepted
            # count): the dispatch thread pays ONE device→host sync per
            # verify.
            packed = jnp.concatenate(
                [out_tokens, accepted[:, None]], axis=1
            )
            return new_tokens, new_positions, caches, packed

        # a family without a verify walk has no speculative program
        self._spec_available = fam.verify_paged is not None
        self._spec_enabled = (
            self._spec_available and ecfg.spec_decode_enable == "on"
        )

        wrap = self._compile_watch.wrap
        # `window` is static: the page kernel has one full-capacity
        # executable, the gather one per power-of-two attention window.
        self._decode_fn = wrap(
            "decode",
            jax.jit(decode_paged, donate_argnums=(1,), static_argnums=(9,)),
        )
        self._update_slots_fn = wrap("update_slots", jax.jit(_update_slots))
        self._extend_fn = wrap(
            "extend",
            jax.jit(extend_packed, donate_argnums=(1,)) if packed
            else jax.jit(
                extend_batch_paged, donate_argnums=(1,), static_argnums=(8,)
            ),
        )
        state_row_keys = tuple(fam.state_row_keys)

        def prefix_state_copy(caches, rows):
            # Row ``rows[0]`` of every fixed-state leaf to row ``rows[1]``
            # (a slot's state out to a prefix entry's store row, or
            # back): in place, the cache donated. The small second output
            # is what the dispatch watcher awaits (nothing donates it).
            src, dst = rows[0], rows[1]
            new = dict(caches)
            for key in state_row_keys:
                new[key] = jax.tree.map(
                    lambda leaf: leaf.at[dst].set(leaf[src]), caches[key]
                )
            return new, rows + 0

        self._copy_state_fn = None
        if self._state_store_rows:
            self._copy_state_fn = wrap(
                "prefix_state_copy",
                jax.jit(prefix_state_copy, donate_argnums=(0,)),
            )
        self._finish_fn = wrap("finish", jax.jit(finish_batch))
        self._put_rows_fn = wrap("put_rows", jax.jit(put_rows))
        self._zero_carries: Dict[int, object] = {}  # _zero_hidden's, by rows
        self._wave_stats: list = []  # (span fields, device counts) of a wave's chunks
        self._wave_span = None  # of the wave's LAST chunk: its first tokens' hand-off waits for that stamp
        self._wave_entries: list = []  # stateful prefix entries the wave in flight inserted
        self._spec_verify_fn = wrap(
            "spec_verify",
            jax.jit(
                spec_verify_paged, donate_argnums=(1,), static_argnums=(11,)
            ),
        )

    # ------------------------------------------------------------------ //
    # public API
    @property
    def metrics(self) -> Dict[str, float]:
        """Legacy flat-dict view over the registry families (the shape of
        the pre-registry ``self.metrics`` dict — the tools and tests
        read these keys; /internal/metrics serves them as JSON).
        Families are process-global, so values accumulate across engine
        instances in one process; consumers read deltas."""
        rb_prefill = _M_READBACK.labels(kind="prefill")
        rb_decode = _M_READBACK.labels(kind="decode")
        out = prefix_cache_mod.metrics_snapshot()
        out.update(spec_decode_mod.metrics_snapshot())
        out.update(kv_pages_mod.metrics_snapshot())
        out.update(scheduler_mod.metrics_snapshot())
        out["paged_attn_kernel_dispatches"] = _M_PAGED_ATTN.labels(
            path="kernel"
        ).value
        out["paged_attn_gather_dispatches"] = _M_PAGED_ATTN.labels(
            path="gather"
        ).value
        out.update({
            "generated_tokens": _M_TOKENS.value,
            "requests": _M_REQUESTS.value,
            "decode_steps": _M_DECODE_STEPS.value,
            "decode_dispatches": _M_DECODE_DISPATCHES.value,
            "admission_waves": _M_WAVES.value,
            "prefill_chunks": _M_PREFILL_CHUNKS.value,
            "prefill_tokens": _M_PREFILL_TOKENS.value,
            "extend_tokens_computed": _M_EXTEND_COMPUTED.value,
            "queue_wait_sum": _M_QUEUE_WAIT.sum,
            "queue_wait_n": _M_QUEUE_WAIT.count,
            "ttft_sum": _M_TTFT.sum,
            "ttft_n": _M_TTFT.count,
            "prefill_wait_sum": _M_PREFILL_WAIT.sum,
            "readback_prefill_wait_sum": rb_prefill.sum,
            "readback_prefill_n": rb_prefill.count,
            "readback_decode_wait_sum": rb_decode.sum,
            "readback_decode_n": rb_decode.count,
            "spec_pipeline_rollbacks": _M_SPEC_PIPE_ROLLBACKS.value,
            "spec_pipeline_confirmed": _M_SPEC_PIPE_CONFIRMED.value,
        })
        # Cumulative dispatch-timeline counters (zeros when the ring is
        # off) — the loadgen scraper differences these into the gated
        # bubble block.
        out.update(dispatch_timeline_mod.counters_snapshot())
        return out

    def utilization_snapshot(self) -> Dict[str, float]:
        """The compile-path stats plus the rolling bubble decomposition
        of the dispatch timeline (``GET /internal/slo`` and the
        black-box bundles read this)."""
        out = self._compile_watch.snapshot()
        if self._dtl is not None:
            out.update(self._dtl.bubble_snapshot())
        return out

    def _kernel_pages_walked(self) -> Dict[str, int]:
        """What the page kernel walks at the FIRST step of the decode
        dispatch about to launch, against the dense ``slots x Pmax``
        grid it replaced — ops/page_attention.page_work_list's count
        from the host's position shadow (caller holds the lock; no
        readback): a live row's pages up to its query position, one
        scratch page per empty slot; and the grid steps that carry
        them, ``_kv_pages_a_step`` pages of a row a step; and the rows a
        page's softmax runs over (ops/page_attention.score_rows: a query
        group's own KV head where the kernel folds the others away)."""
        page = self.engine_config.page_size
        last = self.max_seq_len - 1
        live = [min(p, last) // page + 1 for p in self._slot_pos.values()]
        empty = self.num_slots - len(live)
        n = self._kv_pages_a_step
        counts = {
            "kv_pages_walked": sum(live) + empty,
            # grid steps of the same walk: a step carries up to n pages
            # of one row (kv_pages_walked / kv_page_steps = pages a step)
            "kv_page_steps": sum(-(-m // n) for m in live) + empty,
            "kv_pages_grid": self.num_slots * self._max_pages_per_slot,
        }
        if self._kv_score_rows:  # a latent pool is not this kernel's
            counts["kv_score_rows"] = self._kv_score_rows
        return counts

    def _stream_backlog_tokens(self) -> int:
        """Tokens the reader has emitted that no handler has written yet,
        over the open streams whose handler reports (docs/streaming.md):
        two integers a stream, read without a lock."""
        return sum(
            r.queued - r.written
            for r in list(self._streams.values())
            if r.written is not None
        )

    def submit(
        self, prompt_ids: Sequence[int], params: Optional[SamplingParams] = None
    ) -> _Request:
        """Submit a request; returns its handle (queue + cancellation flag)."""
        params = params or SamplingParams()
        # Over-long prompts keep their TAIL (recency wins in chat), and the
        # clamp reserves a minimum generation budget: clamping to capacity
        # alone would leave 0 decode steps and the request would "answer"
        # with a single token — observed as silently empty RAG responses
        # when a word-budgeted context cap overshoots the cache in engine
        # tokens.
        reserve = max(1, min(64, params.max_tokens))
        # keep >= 1 always: at tiny max_seq_len the reserve can swallow the
        # whole capacity and a -0 / negative slice would keep the over-long
        # prompt, overflowing the prefill bucket and killing the scheduler
        # thread with a numpy broadcast error in _admit.
        keep = max(1, self.max_seq_len - 1 - reserve)
        prompt_ids = list(prompt_ids)[-keep:]
        req = _Request(
            rid=next(_REQ_IDS),
            prompt_ids=prompt_ids,
            params=params,
            sampling_seed=params.seed or _UNSEEDED_RNG.getrandbits(31),
            t_submit=time.time(),
            trace_hex=metrics_mod.current_trace_id_hex(),
        )
        if self._prefix is not None and params.prefix_hint:
            # Session keep-alive: an active session's cached preamble
            # gets its recency bumped at submit time, before admission,
            # so concurrent traffic can't LRU it out between turns.
            self._prefix.touch(params.prefix_hint)
        if flight_recorder.enabled():
            # Map the rid BEFORE the request becomes visible to the
            # dispatch thread: once _pending holds it, admission (and
            # for tiny requests even completion) can race ahead of this
            # thread — a late map_rid would lose events and leak an
            # engine-owned record that no finish_rid ever retires.
            # Server-bound threads carry their request's record; bare
            # submits (facade, tests) open an engine-owned one
            # retired when this rid finishes.
            rec = flight_recorder.current()
            if rec is None:
                rec = flight_recorder.start(
                    trace_id=req.trace_hex, owner="engine"
                )
            flight_recorder.map_rid(req.rid, rec)
            req.flight_rec = rec
            if rec is not None:
                rec.event(
                    "submit", rid=req.rid, prompt_tokens=len(prompt_ids)
                )
        cap = self.engine_config.max_queued_requests
        with self._lock:
            if self._draining:
                # The drain workflow is checkpointing this engine's
                # live requests off to the spool: new work must go to a
                # sibling. EngineOverloaded maps to the same 429/shed
                # path the router already re-places on.
                flight_recorder.event(
                    "engine_draining", pending=len(self._pending)
                )
                flight_recorder.finish_rid(req.rid, "overload")
                raise EngineOverloaded(
                    "engine draining — checkpoint/handover in progress"
                )
            if cap > 0 and len(self._pending) >= cap:
                _M_OVERLOAD.inc()
                flight_recorder.event(
                    "engine_overloaded", pending=len(self._pending), cap=cap
                )
                # The rid never entered the queue: retire engine-owned
                # records (or just unmap server-owned ones) so the
                # rejected submit cannot leak an open timeline.
                flight_recorder.finish_rid(req.rid, "overload")
                raise EngineOverloaded(
                    f"engine admission queue full "
                    f"({len(self._pending)}/{cap} pending)"
                )
            self._pending.append(req)
            _M_QUEUE_DEPTH.set(len(self._pending))
            _M_REQUESTS.inc()
            self._lock.notify_all()
        return req

    def queue_depth(self) -> int:
        """Requests awaiting admission (the server's shedding signal)."""
        with self._lock:
            return len(self._pending)

    def abort(self, handle) -> bool:
        """Abort a request by handle (the ``submit()`` return) or rid.

        Pending requests are failed immediately (queue slot returned,
        consumer unblocked with the end sentinel); slotted requests are
        marked cancelled and released by the dispatch loop's next pass —
        freeing the decode slot and any prefix-cache pins mid-decode
        instead of burning steps to max_tokens. Returns False when the
        request is unknown or already finished."""
        with self._lock:
            req: Optional[_Request] = None
            if isinstance(handle, _Request):
                req = handle
            else:
                rid = int(handle)
                req = next(
                    (r for r in self._pending if r.rid == rid), None
                ) or next(
                    (r for r in self._slot_req.values() if r.rid == rid), None
                ) or self.scheduler.find_rid(rid)
            if req is None or req.finished or req.cancelled:
                return False  # unknown, done, or already aborted
            req.cancelled = True
            _M_ABORTS.inc()
            flight_recorder.event_rid(
                req.rid, "abort", slotted=req.slot >= 0
            )
            if req.slot < 0:
                # Not admitted yet: remove the tombstone now so it never
                # claims a slot (admission also tolerates cancelled
                # entries it still finds in the deque).
                try:
                    self._pending.remove(req)
                    _M_QUEUE_DEPTH.set(len(self._pending))
                except ValueError:
                    pass
                req.finished = True
                req.out_queue.put(_END)
                flight_recorder.finish_rid(req.rid, "abort")
            else:
                # Wake the dispatch loop for the eager slot release.
                self._lock.notify_all()
            return True

    def generate_ids(
        self, prompt_ids: Sequence[int], params: Optional[SamplingParams] = None
    ) -> "queue.Queue[Optional[int]]":
        """Submit a request; returns the queue of generated token ids."""
        return self.submit(prompt_ids, params).out_queue

    def iter_ids(
        self,
        prompt_ids: Sequence[int],
        params: Optional[SamplingParams] = None,
        timeout: Optional[float] = None,
    ) -> Generator[int, None, None]:
        """Submit a request and yield generated token ids as they decode.
        ``timeout=None`` falls back to the ``stream_timeout_s`` knob,
        applied as a STALL deadline per awaited token (a healthy long
        stream never times out); an explicit ``timeout`` is an absolute
        whole-stream budget (per-request deadlines)."""
        stall_s = (
            float(self.engine_config.stream_timeout_s) if timeout is None else None
        )
        req = self.submit(prompt_ids, params)
        deadline = None if timeout is None else time.time() + timeout
        try:
            while True:
                for item in _next_stream_items(req.out_queue, stall_s, deadline):
                    if item is _END:
                        if req.error is not None:
                            if isinstance(req.error, RequestPreempted):
                                # Typed pass-through: the stream layer needs
                                # the snapshot id to advertise a restore
                                # target instead of a bare 5xx.
                                raise req.error
                            raise RuntimeError("LLM engine failed") from req.error
                        return
                    yield item
        finally:
            self.abort(req)

    def stream_text(
        self,
        prompt_ids: Sequence[int],
        params: Optional[SamplingParams] = None,
        timeout: Optional[float] = None,
    ) -> Generator[str, None, None]:
        """Generate and yield incremental detokenized text chunks.

        The submit happens EAGERLY (not on first iteration), so
        admission-queue overload raises ``EngineOverloaded`` at the call
        site — where the chain-server can still answer 429 — rather than
        mid-SSE-stream. ``timeout=None`` uses the ``stream_timeout_s``
        knob as a per-token stall deadline; per-request deadlines pass
        their remaining budget as an absolute whole-stream cap.
        """
        params = params or SamplingParams()
        req = self.submit(prompt_ids, params)
        gen = self._stream_from(req, params, timeout)
        # close() on a NEVER-STARTED generator skips its finally (PEP
        # 342), so a caller that submits but aborts before the first
        # next() — e.g. the server failing resp.prepare() on a gone
        # client — would leak the request to max_tokens. The finalizer
        # guarantees the abort on GC; abort() is idempotent, so the
        # started path's finally stays the prompt owner.
        weakref.finalize(gen, self.abort, req)
        return gen

    def _stream_from(
        self,
        req: _Request,
        params: SamplingParams,
        timeout: Optional[float],
        prior_ids: Optional[Sequence[int]] = None,
    ) -> Generator[str, None, None]:
        """One item per wake-up: a ``TokenBlock`` of everything the
        reader handed over since the last one, a delta a token, at the
        same cost for the 3000th token as for the first
        (docs/streaming.md). Restored requests pre-seed the decode
        context with the tokens the dead engine already emitted, with
        nothing delivered: the first delta yields the full spooled
        prefix plus the new token with exact tokenization boundaries;
        the router trims it against its forwarded-character offset."""
        dec = IncrementalDecoder(self.tokenizer, prior_ids or (), params.stop)
        stall_s = (
            float(self.engine_config.stream_timeout_s) if timeout is None else None
        )
        deadline = None if timeout is None else time.time() + timeout

        def written(n: int, t_put: float) -> None:
            req.written = (req.written or 0) + n
            if t_put:  # 0.0: the reader gave no time (the timeline is off)
                _M_WRITE_LAG.observe(time.time() - t_put, trace_id=None)

        t_put = 0.0  # when the reader put the oldest ids of the next hand-off

        taken = 0  # ids consumed since the last hand-off (one may add no text yet)
        self._streams[id(req)] = req
        try:
            while True:
                items = _next_stream_items(req.out_queue, stall_s, deadline)
                t_put = t_put or req.out_queue.t_taken
                ended = items[-1] is _END
                if ended:
                    items.pop()
                taken += len(items)
                deltas = list(map(dec.push, items))
                if ended and req.error is None:
                    # Flush the held-back tail: a stream that ends inside
                    # a multi-byte sequence (random weights do ~1/3 of
                    # the time; max_tokens can truncate a real model
                    # there) would otherwise arrive without it.
                    deltas.append(dec.flush())
                pieces = [d for d in deltas if d]
                if pieces:
                    _M_HANDOFFS.inc()
                    _M_HANDOFF_TOKENS.inc(taken)
                    yield TokenBlock(
                        pieces, taken, functools.partial(written, t_put=t_put)
                    )
                    taken, t_put = 0, 0.0
                if dec.stopped:
                    return
                if ended:
                    if isinstance(req.error, RequestPreempted):
                        raise req.error
                    if req.error is not None:
                        raise RuntimeError("LLM engine failed") from req.error
                    return
        finally:
            # Consumer gone (disconnect/timeout/stop hit): abort releases
            # the slot and any prefix pins at the next dispatch pass
            # instead of burning steps to max_tokens.
            self._streams.pop(id(req), None)
            self.abort(req)

    def chat(
        self, messages: Sequence[Tuple[str, str]], params: Optional[SamplingParams] = None
    ) -> Generator[str, None, None]:
        """Render the chat template and stream the completion."""
        return self.stream_text(self.tokenizer.render_chat(messages), params)

    def is_decoding(self) -> bool:
        """Whether any request currently occupies a decode slot (public —
        the embedder's ingestion throttle polls this)."""
        with self._lock:
            return bool(self._slot_req)

    def hold_admissions(self):
        """Context manager: pause admissions while requests enqueue, so the
        dispatch thread sees them all at once and admits one full wave."""
        engine = self

        class _Hold:
            def __enter__(self):
                with engine._lock:
                    engine._paused = True

            def __exit__(self, *exc):
                with engine._lock:
                    engine._paused = False
                    engine._lock.notify_all()
                return False

        return _Hold()

    # ------------------------------------------------------------------ //
    # Preemption tolerance (docs/resilience.md, "Preemption and drain
    # lifecycle"): drain-with-checkpoint on the way down, snapshot
    # restore on the way back up. The dispatch-thread halves live in
    # _process_restores/_apply_restore next to the loop they serve.

    @property
    def snapshot_spool(self) -> request_snapshot_mod.SnapshotSpool:
        """The engine's on-disk snapshot spool (the server's
        /internal/snapshots endpoints list and relay documents through
        this; fingerprint-stamped at engine build)."""
        return self._spool

    def is_draining(self) -> bool:
        with self._lock:
            return self._draining

    def drain(self, timeout: Optional[float] = None) -> Dict[str, object]:
        """Quiesce this engine and checkpoint every in-flight request.

        The workflow (each step gated on the previous): (1) flip
        ``_draining`` — submits start refusing, the disagg prefill tier
        stops claiming waves, and the dispatch loop parks at its next
        block boundary; (2) wait for the park acknowledgement plus a
        zero in-flight prefill wave count; (3) push a FIFO-last barrier
        through the readback queue so every already-dispatched slab is
        emitted and each request's position/transcript is current;
        (4) capture queued tier-crossing handoffs and slotted requests
        into the spool (page-granular KV payload), fail their streams
        with the typed ``RequestPreempted`` carrying the snapshot id,
        and release their slots/pages. Requests that cannot carry KV
        (unadmitted, non-paged layout, or a missed park deadline)
        become replay-only preemptions — the router re-places them from
        the original prompt, so nothing is ever silently lost.

        Runs on the caller's (HTTP) thread; bounded by
        ``engine.drain_timeout_s`` unless ``timeout`` overrides it.
        Returns the summary the router's drain report consumes."""
        request_snapshot_mod.require_paged_state(self, "drain")
        budget_s = float(
            self.engine_config.drain_timeout_s if timeout is None else timeout
        )
        deadline = time.time() + budget_s
        flight_recorder.event("drain_begin", timeout_s=round(budget_s, 3))
        with self._lock:
            self._draining = True
            self._paused = True
            self._lock.notify_all()
            while self._running and (
                not self._drain_parked or self.scheduler.wave_inflight() > 0
            ):
                if time.time() >= deadline:
                    break
                self._lock.wait(timeout=0.05)
            parked = self._drain_parked and self.scheduler.wave_inflight() == 0
        # Queued restores can never run against a parked loop — fail
        # them now so their waiters fall back to replay on a sibling.
        while True:
            try:
                entry = self._restore_q.get_nowait()
            except queue.Empty:
                break
            entry[3]["mode"] = "replay_needed"
            entry[3]["event"].set()
        if parked:
            # FIFO-last readback barrier: when the reader sets it,
            # every earlier slab/prefill readback has been emitted and
            # req.position / req.emitted are current. Enqueued from
            # THIS thread only after the park, so no late dispatch can
            # slip a readback in behind it.
            barrier = threading.Event()
            self._readback.put(("drain_barrier", barrier, []))
            if not barrier.wait(timeout=max(0.05, deadline - time.time())):
                logger.error(
                    "drain readback barrier missed the deadline — "
                    "falling back to replay-only checkpoints"
                )
                parked = False
        if not parked:
            logger.error(
                "engine did not park within the %.1f s drain budget — "
                "in-flight requests will be preempted replay-only "
                "(prompt + pinned seed; no KV payload)", budget_s,
            )
        # Reader-side releases pend while the loop is parked: apply
        # them so already-finished requests release, not checkpoint.
        self._drain_releases()
        handoff_victims = []  # records needing checkpoint-or-complete
        slot_victims: List[Tuple[_Request, int]] = []
        with self._lock:
            # Tier-crossing handoffs the decode tier never imported
            # (prefill done, KV funded, sitting in the TransferQueue):
            # these MUST be checkpointed or completed, never dropped.
            for rec in self.scheduler.drain_handoffs():
                handoff_victims.append(rec)
            for slot, req in list(self._slot_req.items()):
                if req.finished:
                    self._release(slot, req)
                    continue
                slot_victims.append((req, slot))
            pending = list(self._pending)
            self._pending.clear()
            _M_QUEUE_DEPTH.set(0)
        snapshots: List[str] = []
        replayed = 0
        completed = 0

        def _preempt(req: _Request, slot: int, position: int,
                     pages: Tuple[int, ...]) -> Optional[str]:
            """Capture + spool + fail one live request. Returns the
            snapshot id when a KV payload was spooled (restore path),
            None for replay-only."""
            nonlocal replayed
            cap_pos = position if (parked and pages and req.emitted) else 0
            snap = request_snapshot_mod.capture(
                self, req, cap_pos, pages if cap_pos else ()
            )
            sid: Optional[str] = None
            if snap.restorable:
                try:
                    self._spool.save(snap)
                    sid = snap.snapshot_id
                    snapshots.append(sid)
                except OSError as exc:
                    logger.error(
                        "snapshot spool write failed for rid %d: %s",
                        req.rid, exc,
                    )
            mode = "snapshot" if sid else "replay"
            if sid is None:
                replayed += 1
            request_snapshot_mod.record_preempted(mode)
            flight_recorder.event_rid(
                req.rid, "preempt", mode=mode, snapshot=sid or "",
                position=position, generated=req.generated,
            )
            req.error = RequestPreempted(
                f"request preempted by engine drain ({mode})",
                snapshot_id=sid,
            )
            req.finished = True
            req.out_queue.put(_END)
            flight_recorder.finish_rid(req.rid, "preempt")
            return sid

        for rec in handoff_victims:
            req = rec.req
            if req.cancelled and not req.finished:
                # Abort-during-drain: the dispatch pass that would have
                # emitted its end sentinel is parked — emit it here.
                req.finished = True
                req.out_queue.put(_END)
                flight_recorder.finish_rid(req.rid, "abort")
            if req.finished:
                completed += 1
            else:
                with self._lock:
                    pages = tuple(self._slot_pages.get(rec.slot, ()))
                _preempt(req, rec.slot, int(rec.position), pages)
            # req.finished is set either way, so the handoff import's
            # finished branch performs the full cleanup: pages, slot,
            # spec-proposer state, prefix pins.
            self._import_handoff(rec)
        for req, slot in slot_victims:
            if req.cancelled:
                req.finished = True
                req.out_queue.put(_END)
                flight_recorder.finish_rid(req.rid, "abort")
                completed += 1
            else:
                with self._lock:
                    pages = tuple(self._slot_pages.get(slot, ()))
                _preempt(req, slot, int(req.position), pages)
            with self._lock:
                self._release(slot, req)
        for req in pending:
            # Never admitted: nothing on device — replay-only, and the
            # router re-places it from the original request body.
            if req.cancelled or req.finished:
                if not req.finished:
                    req.finished = True
                    req.out_queue.put(_END)
                    flight_recorder.finish_rid(req.rid, "abort")
                completed += 1
                continue
            _preempt(req, -1, 0, ())
        summary: Dict[str, object] = {
            "draining": True,
            "parked": parked,
            "preempted": len(snapshots) + replayed,
            "spooled": len(snapshots),
            "snapshots": snapshots,
            "replay_only": replayed,
            "completed": completed,
        }
        flight_recorder.event(
            "drain_complete", spooled=len(snapshots), replay_only=replayed,
            parked=parked,
        )
        logger.warning(
            "engine drained: %d spooled, %d replay-only, %d completed "
            "(parked=%s)", len(snapshots), replayed, completed, parked,
        )
        return summary

    def resume_from_drain(self) -> None:
        """Lift the drain: admission reopens and the dispatch loop
        resumes. (The chaos harness's graceful path relaunches the
        process instead; this serves drain-then-undrain operations.)"""
        with self._lock:
            self._draining = False
            self._drain_parked = False
            self._paused = False
            self._lock.notify_all()
        logger.warning("engine drain lifted; admission reopened")

    def restore_snapshot(
        self, snap: "request_snapshot_mod.RequestSnapshot"
    ) -> Tuple[_Request, SamplingParams, List[int], str]:
        """Re-admit a spooled snapshot on THIS engine.

        Returns ``(req, params, prior_ids, mode)`` — mode "restore"
        resumes decode token-identically from the snapshot position
        (stream it with :meth:`stream_restored`, which re-delivers the
        spooled prefix with exact tokenization boundaries); mode
        "replay" regenerates from the prompt under the PINNED sampling
        seed (prior_ids empty — same final text for deterministic
        sampling, re-delivered from the start). Raises
        ``SnapshotMismatch`` on config-fingerprint or KV-geometry
        drift and ``EngineOverloaded`` while this engine drains."""
        request_snapshot_mod.require_paged_state(self, "restore_snapshot")
        t0 = time.time()
        self._spool.check_fingerprint(snap)
        request_snapshot_mod.check_geometry(self, snap)
        params = snap.sampling_params()
        with self._lock:
            if self._draining:
                raise EngineOverloaded(
                    "engine draining — cannot accept restores"
                )
        if not (snap.restorable and snap.emitted and snap.position > 0):
            req = self.submit(snap.prompt_ids, params)
            request_snapshot_mod.record_restored("replay")
            flight_recorder.event_rid(
                req.rid, "restore", snapshot=snap.snapshot_id, mode="replay"
            )
            return req, params, [], "replay"
        payload = request_snapshot_mod.decode_kv_payload(snap.kv)
        req = _Request(
            rid=next(_REQ_IDS),
            prompt_ids=list(snap.prompt_ids),
            params=params,
            sampling_seed=int(snap.sampling_seed),
            t_submit=time.time(),
            trace_hex=metrics_mod.current_trace_id_hex(),
        )
        req.emitted = list(snap.emitted)
        req.generated = len(req.emitted)
        req.position = int(snap.position)
        if flight_recorder.enabled():
            rec = flight_recorder.current()
            if rec is None:
                rec = flight_recorder.start(
                    trace_id=req.trace_hex, owner="engine"
                )
            flight_recorder.map_rid(req.rid, rec)
            req.flight_rec = rec
        result: Dict[str, object] = {
            "event": threading.Event(), "mode": None, "error": None,
        }
        with self._lock:
            self._restore_q.put((snap, payload, req, result))
            self._lock.notify_all()
        if not result["event"].wait(
            timeout=float(self.engine_config.drain_timeout_s)
        ):
            flight_recorder.finish_rid(req.rid, "error")
            raise TimeoutError(
                "restore was not picked up by the dispatch loop"
            )
        if result["error"] is not None:
            flight_recorder.finish_rid(req.rid, "error")
            raise result["error"]  # type: ignore[misc]
        if result["mode"] != "restore":
            # No free slot/pages right now: fall back to a full replay
            # through normal admission (the FIFO queue absorbs the
            # wait; the pinned seed keeps the text identical).
            flight_recorder.finish_rid(req.rid, "restore_replay")
            req2 = self.submit(snap.prompt_ids, params)
            request_snapshot_mod.record_restored("replay")
            flight_recorder.event_rid(
                req2.rid, "restore", snapshot=snap.snapshot_id, mode="replay"
            )
            return req2, params, [], "replay"
        request_snapshot_mod.record_restored("restore", time.time() - t0)
        flight_recorder.event_rid(
            req.rid, "restore", snapshot=snap.snapshot_id, mode="restore",
            position=req.position, emitted=req.generated,
        )
        return req, params, list(snap.emitted), "restore"

    def stream_restored(
        self,
        req: _Request,
        params: SamplingParams,
        prior_ids: Sequence[int],
        timeout: Optional[float] = None,
    ) -> Generator[str, None, None]:
        """Stream a restored request: the spooled transcript pre-seeds
        the decode context, so the client receives the full prefix text
        plus the live continuation with exact tokenization boundaries
        (see _stream_from's prior_ids contract)."""
        gen = self._stream_from(req, params, timeout, prior_ids=prior_ids)
        weakref.finalize(gen, self.abort, req)
        return gen

    def _quiesce_for_warmup(self, what: str) -> bool:
        """Wait, admissions held by the caller, until live decode and the
        scheduler's tiers are quiet, before a warm walk dispatches from
        ITS thread: the walk's programs donate ``self._cache`` and so
        does the dispatch thread's ``_decode_fn`` (concurrent donation
        is a use-after-free), and a disagg prefill wave mid-flight or an
        un-imported handoff holds the same cache chain. False: the
        engine stopped meanwhile."""
        quiesce_s = float(self.engine_config.quiesce_timeout_s)
        deadline = time.time() + quiesce_s
        with self._lock:
            while (
                self._slot_req or self.scheduler.tier_busy()
            ) and self._running:
                if time.time() > deadline:
                    raise TimeoutError(
                        f"{what}: live decode did not quiesce within "
                        f"{quiesce_s:.0f} s"
                    )
                self._lock.wait(timeout=0.2)
            return self._running

    def warmup(self) -> None:
        """Pre-compile every serving shape, so that no request meets an
        XLA compile (tens of seconds each on a cold engine).

        The WHOLE prefill set directly: one extend per
        ``shapes.extend_signatures()`` entry (rows, width, window; for a
        packed family one per token rung, whose carry has the one row
        count), one finish per wave rung and one ``put_rows`` per pair
        of a wave rung and a smaller one. Zero-valid rows make every
        dispatch a value-level no-op on the caches, so this needs no
        scheduler involvement, and the set covers every prompt length
        up to max_seq_len: a prompt of at most one chunk is chunk 0 of
        the same walk. Then the page-table scatter, the decode block at
        every window rung and, where enabled, the spec verify shapes.
        """
        import jax.numpy as jnp

        signatures = self.shapes.extend_signatures()
        row_rungs = sorted({n for n, _, _ in signatures})
        with self._compile_watch.warmup_scope(), self.hold_admissions():
            if not self._quiesce_for_warmup("warmup"):
                return
            if self._copy_state_fn is not None:
                # the state copy of a prefix save / restore: a store
                # row onto itself, a no-op on the values
                rows = jnp.asarray(np.full((2,), self.num_slots, np.int32))
                self._cache, _ = self._copy_state_fn(self._cache, rows)
            for n in row_rungs:
                zeros_n = jnp.zeros((n,), jnp.int32)
                last_h = self._zero_hidden(n)
                for _, width, W in (s for s in signatures if s[0] == n):
                    # zero-valid rows route every write to the
                    # scratch page — value-level no-ops even when
                    # slot 0's table holds stale entries
                    if self.shapes.packed:
                        # (no live row: every token of the axis is dead)
                        last_h, self._cache, *_ = self._extend_fn(
                            self.params, self._cache,
                            jnp.zeros((width,), jnp.int32),
                            jnp.zeros((5, n), jnp.int32),
                            jnp.zeros((2,), jnp.int32), last_h,
                            self._tables_dev,
                        )
                        continue
                    last_h, self._cache, *_ = self._extend_fn(
                        self.params, self._cache,
                        jnp.zeros((n, width), jnp.int32), zeros_n, zeros_n,
                        zeros_n, last_h, self._tables_dev, W,
                    )
                for m in (m for m in row_rungs if m < n):
                    last_h = self._put_rows_fn(
                        last_h, jnp.full((m,), n, jnp.int32),
                        self._zero_hidden(m),
                    )
                first = self._finish_fn(
                    self.params,
                    last_h,
                    zeros_n,
                    jnp.ones((n,), jnp.int32),
                    jnp.zeros((n,), jnp.float32),
                    jnp.ones((n,), jnp.float32),
                    jnp.zeros((n,), jnp.int32),
                )
                # admission's slot update on first tokens of finish's
                # kind (they carry the mesh in their type): the slot
                # index is out of range, so the scatter drops every row
                (
                    self._tokens_dev,
                    self._positions_dev,
                    self._temps_dev,
                    self._topps_dev,
                    self._seeds_dev,
                ) = self._update_slots_fn(
                    self._tokens_dev, self._positions_dev, self._temps_dev,
                    self._topps_dev, self._seeds_dev,
                    jnp.full((n,), self.num_slots, jnp.int32), first,
                    zeros_n, jnp.zeros((n,), jnp.float32),
                    jnp.ones((n,), jnp.float32), zeros_n,
                )
                self._tokens_dev.block_until_ready()
            # Warm the page-table scatter at every funded-wave row
            # count (1..num_slots — _fund_paged_admissions scatters
            # exactly the funded rows, unpadded): all-zero rows
            # point at the reserved scratch page, the same state
            # the tables start in, and admission rewrites a slot's
            # row before any live dispatch reads it. Without this
            # walk the FIRST real admission wave of each size paid
            # the scatter compile mid-serving — found by the
            # compile watch the moment it landed (hot_path_total=2
            # on the first cpu_smoke run).
            for n in range(1, self.num_slots + 1):
                self._tables_dev = self._tables_fn(
                    self._tables_dev,
                    jnp.zeros((n,), jnp.int32),
                    jnp.zeros(
                        (n, self._max_pages_per_slot), jnp.int32
                    ),
                )
            self._tables_dev.block_until_ready()
            # Warm the decode executables with dead dispatches
            # (live all-False routes every write to the scratch page
            # — value-level no-ops): the kernel path has ONE
            # full-capacity program, the gather path one per window
            # rung. Without this, the first measured decode of a
            # cpu_smoke/loadgen run paid the compile (the hole PR 9
            # closed for prefill shapes, reopened by the kernel's
            # new executable family).
            # On the slot arrays themselves (their outputs dropped): jit
            # keys an executable on an operand's kind, and fresh
            # jnp.zeros here built one per rung that serving never ran.
            dead = np.zeros((self.num_slots,), bool)
            rungs = (
                [self.max_seq_len] if self._paged_kernel
                else self.shapes.window_rungs()
            )
            for w in rungs:
                (_, _, self._cache, slab) = self._decode_fn(
                    self.params, self._cache, self._tokens_dev,
                    self._positions_dev, self._temps_dev, self._topps_dev,
                    self._seeds_dev, self._tables_dev, dead, w,
                )
                slab.block_until_ready()
        # Spec verify executables (one per window rung) compile here so
        # a verify dispatch never compiles inside a request (a warm-up
        # scope of their own: runtime-toggle callers run them alone).
        if self._spec_enabled:
            self.warmup_spec_shapes()
        # Arm hot-path compile detection: every signature compiled above
        # (plus anything later warm scopes add) is the pre-warmed rung
        # set; a first-seen signature from here on is a loud incident.
        self._compile_watch.finish_warmup()

    def shutdown(self) -> bool:
        """Stop the dispatch/reader/watchdog threads. Returns True on a
        clean join; a thread still alive past the join timeout (wedged
        dispatch, stuck device call) is LOGGED as an error and flips the
        wedged gauge/readiness instead of silently returning as if the
        shutdown were clean."""
        with self._lock:
            self._running = False
            self._lock.notify_all()
        self._wd_stop.set()
        self._thread.join(timeout=10)
        self._reader.join(timeout=10)
        sched_ok = self.scheduler.stop()
        if self._watchdog is not None:
            self._watchdog.join(timeout=2)
        stuck = [t.name for t in (self._thread, self._reader) if t.is_alive()]
        if not sched_ok:
            stuck.append("llm-prefill-tier")
        if stuck:
            logger.error(
                "engine shutdown left live thread(s) %s after the 10 s "
                "join timeout — marking the engine wedged instead of "
                "reporting a clean shutdown",
                ", ".join(stuck),
            )
            self._mark_wedged(f"shutdown join timeout: {', '.join(stuck)}")
            return False
        return True

    def _mark_wedged(self, reason: str) -> None:
        self._wedged = True
        _M_WEDGED.set(1)
        ENGINE_WEDGED.set()
        logger.error("engine wedged: %s", reason)
        # Where every thread stands: a wedge is a thread that waits for
        # another, and the log is what is left when the machine is gone.
        import sys
        import traceback

        names = {t.ident: t.name for t in threading.enumerate()}
        for ident, frame in sys._current_frames().items():
            logger.error(
                "wedged: thread %s\n%s", names.get(ident, ident),
                "".join(traceback.format_stack(frame, limit=12)),
            )
        # Anomaly black box: a wedged dispatch loop is exactly the
        # moment whose state an investigation needs (utils/blackbox.py;
        # one boolean read when disabled, runs on the watchdog thread).
        from generativeaiexamples_tpu.utils import blackbox

        blackbox.notify_wedged(reason)

    def _clear_wedged(self) -> None:
        if self._wedged:
            self._wedged = False
            _M_WEDGED.set(0)
            ENGINE_WEDGED.clear()
            logger.warning("engine dispatch loop recovered; wedged state cleared")

    def _watchdog_loop(self) -> None:
        """Detect a dispatch loop that stopped making progress while
        work is outstanding (hung device call, deadlocked dispatch) and
        flip readiness + the genai_engine_wedged gauge. Self-clearing:
        if the loop resumes, the gauge and readiness recover."""
        threshold = float(self.engine_config.watchdog_stall_s)
        poll = max(0.05, min(1.0, threshold / 4))
        while True:
            if self._wd_stop.wait(timeout=poll):
                return
            with self._lock:
                if not self._running:
                    return
                busy = (
                    bool(self._slot_req)
                    or bool(self._pending)
                    or self.scheduler.tier_busy()
                )
                stall = time.time() - self._last_progress
            if busy and stall > threshold:
                if not self._wedged:
                    self._mark_wedged(
                        f"dispatch loop made no progress for {stall:.1f} s "
                        f"with work outstanding (threshold "
                        f"{threshold:.1f} s)"
                    )
            else:
                self._clear_wedged()

    # ------------------------------------------------------------------ //
    # decode loop (dispatch thread): never blocks on the device or host —
    # it chains async device work and hands result handles to the reader.
    # The dispatch-root marker makes that contract machine-checked: the
    # dispatch-readback lint flags blocking syncs anywhere reachable
    # from here (docs/static_analysis.md).
    def _loop(self) -> None:  # genai-lint: dispatch-root
        while True:
            with self._lock:
                while (
                    self._running
                    and (
                        # Draining: once parked at the block boundary,
                        # stay parked until resume_from_drain() or
                        # shutdown — the drain thread owns live state.
                        self._drain_parked
                        if self._draining
                        else (
                            not self.scheduler.has_work()
                            and not self._slot_req
                            and self._release_q.empty()
                            and self._restore_q.empty()
                        )
                    )
                ):
                    # Waiting idle (or held by warmup, or parked by a
                    # drain) IS progress as far as the watchdog cares —
                    # only a stall inside the dispatch body below counts
                    # as wedged. Under disagg an idle decode tier must
                    # not mask a wedged prefill tier: its wave
                    # completions bump _last_progress themselves, so
                    # only credit the idle wait while every tier is
                    # genuinely idle. A parked drain always credits:
                    # queued handoffs awaiting checkpoint are the drain
                    # thread's work, not this loop's.
                    if self._draining or not self.scheduler.tier_busy():
                        self._last_progress = time.time()
                    self._lock.wait(timeout=1.0)
                stopping = not self._running
                parking = self._draining and not self._drain_parked
                self._last_progress = time.time()
            if stopping:
                # Land any in-flight pipelined verify first so its
                # already-computed tokens reach the reader queue ahead
                # of the sentinel (otherwise the final round of every
                # live stream would vanish at shutdown).
                if self._spec_pending is not None:
                    self._flush_spec_pipeline()
                # put() outside the lock: if the runahead queue is full the
                # reader needs the lock (inside _emit) to drain it — putting
                # while holding the lock would deadlock both threads.
                self._readback.put(None)  # reader drains + exits
                return
            if parking:
                # Drain park (docs/resilience.md): land the in-flight
                # pipelined verify so its already-computed tokens reach
                # the reader ahead of the drain thread's readback
                # barrier, then acknowledge the park. No dispatch runs
                # past this point until resume_from_drain()/shutdown —
                # which is exactly what lets the drain thread read KV
                # pages and release slots from outside this thread.
                if self._spec_pending is not None:
                    self._flush_spec_pipeline()
                with self._lock:
                    self._drain_parked = True
                    self._lock.notify_all()
                continue

            try:
                faults_mod.fault_point("engine.dispatch")
                # Chaos-harness kill site: a 'kill' rule here SIGKILLs
                # the replica mid-decode — the spot-VM preemption the
                # fleet gate must survive with zero lost requests.
                faults_mod.fault_point("replica.kill")
                self._drain_releases()
                self._process_restores()
                # Admission through the scheduler seam: the unified
                # policy claims + prefills a wave inline (the exact
                # pre-scheduler order); disagg imports completed
                # handoffs from the prefill tier instead.
                self.scheduler.admit()
                self._decode_if_busy()
            except Exception as exc:  # noqa: BLE001
                logger.exception("decode loop error: %s", exc)
                with self._lock:
                    for slot, req in list(self._slot_req.items()):
                        req.error = exc
                        req.finished = True
                        req.out_queue.put(_END)
                        flight_recorder.finish_rid(req.rid, "error")
                        self._release(slot, req)

    def _decode_if_busy(self) -> None:
        """The loop's decode step: one block (or speculative round)
        while any slot holds a request. The unified policy runs the same
        step between the chunks of a wave (docs/scheduler.md)."""
        with self._lock:
            busy = bool(self._slot_req)
        if busy:
            self._decode_once()

    def _drain_releases(self) -> None:
        while True:
            try:
                slot, req = self._release_q.get_nowait()
            except queue.Empty:
                return
            with self._lock:
                self._release(slot, req)

    def _process_restores(self) -> None:
        """Dispatch-thread snapshot-restore executor (the _restore_q
        comment in __init__ has the why): claims a slot and pages,
        uploads the snapshot's KV payload and slot state, and registers
        the request through the handoff import seam — with no decode
        dispatch in between, so the freshly written position row cannot
        be zeroed as a dead slot by a concurrent decode block."""
        while True:
            try:
                snap, payload, req, result = self._restore_q.get_nowait()
            except queue.Empty:
                return
            try:
                result["mode"] = self._apply_restore(snap, payload, req)
            except Exception as exc:  # noqa: BLE001 - reported to the waiter
                logger.exception(
                    "snapshot %s restore failed: %s", snap.snapshot_id, exc
                )
                result["error"] = exc
            finally:
                result["event"].set()

    def _apply_restore(self, snap, payload, req: _Request) -> str:
        """Re-admit one decoded snapshot (dispatch thread). Returns
        "restore" on success or "replay_needed" when no slot/pages are
        free — the waiting thread then falls back to a plain replay
        submit through normal admission backpressure.

        Device-state invariant being rebuilt: KV rows [0, position)
        hold prompt + all-but-last emitted token, tokens_dev[slot] is
        emitted[-1] (the NEXT decode input — its KV row is written by
        the first restored step), positions_dev[slot] is the snapshot
        position. Rows at/after position are stale garbage until
        overwritten, exactly like a recycled slot — position masking
        already hides them from attention.
        """
        import jax.numpy as jnp

        from generativeaiexamples_tpu.engine.scheduler import handoff as handoff_mod

        page = self.engine_config.page_size
        pos = int(snap.position)
        n_payload = int((snap.geometry or {}).get("pages") or 0)
        with self._lock:
            if not self._free_slots:
                return "replay_needed"
            slot = self._free_slots.pop()
        total = kv_pages_mod.pages_needed(
            pos, max(1, req.params.max_tokens - req.generated), page,
            self.max_seq_len, self._page_slack,
        )
        total = max(total, n_payload)
        pages = self._kv_alloc.alloc(total, count_failure=False)
        while (
            pages is None
            and self._prefix is not None
            and self._prefix.evict_lru()
        ):
            pages = self._kv_alloc.alloc(total, count_failure=False)
        if pages is None:
            kv_pages_mod.record_alloc_failure()
            with self._lock:
                self._free_slots.append(slot)
            return "replay_needed"
        req.slot = slot
        with self._lock:
            # paged_stats() iterates this dict under the lock from
            # scraper threads (same contract as admission funding)
            self._slot_pages[slot] = list(pages)
            self._shared_pages_stale = True
        slots_h, rows_h = self._table_stage_arrays(1)
        slots_h[0] = slot
        rows_h[0, : len(pages)] = pages
        slots_dev = jnp.asarray(slots_h)
        rows_dev = jnp.asarray(rows_h)
        idx_dev = jnp.asarray(np.asarray(pages[:n_payload], np.int32))  # genai-lint: disable=dispatch-readback -- pages is the allocator's host-side Python list; np.asarray copies host ints, no device buffer is synced
        with self._dispatch_lock:
            # genai-lint: disable=shape-cardinality -- single-row scatter: warmup walks every count 1..num_slots, so the 1-row rung is pre-compiled
            self._tables_dev = self._tables_fn(
                self._tables_dev, slots_dev, rows_dev
            )
            # KV payload upload + slot-state writes are EAGER ops: a
            # restore runs once per preempted request (not on the
            # serving hot path), and eager mode neither donates the
            # live cache buffers nor registers with the hot-path
            # compile watch — the chaos gate's zero-post-warmup-compile
            # assertion stays about the serving executables.
            request_snapshot_mod.upload_kv_payload(self._cache, idx_dev, payload)
            self._tokens_dev = self._tokens_dev.at[slot].set(
                int(req.emitted[-1])
            )
            self._positions_dev = self._positions_dev.at[slot].set(pos)
            self._temps_dev = self._temps_dev.at[slot].set(
                float(req.params.temperature)
            )
            self._topps_dev = self._topps_dev.at[slot].set(
                float(req.params.top_p)
            )
            self._seeds_dev = self._seeds_dev.at[slot].set(
                int(req.sampling_seed) & 0x7FFFFFFF
            )
        # Spec-decode context: the host-side proposers (n-gram/lookup)
        # rebuild their window from the transcript; the resident-draft
        # proposer's draft KV is NOT part of the snapshot, so restored
        # rows opt out of drafting under it (they still ride verify
        # dispatches as single-token rows).
        spec_tokens = None
        spec_prop = self._spec_proposer
        if (
            self._spec_enabled
            and spec_prop is not None
            and not spec_prop.uses_draft_model
            and spec_prop.eligible(req.params)
        ):
            spec_tokens = list(req.prompt_ids) + list(req.emitted)
        # Floor at 1: a zero budget would eager-release the slot with
        # no end sentinel ever emitted (the stream would hang); with
        # one step, _emit's done predicate finishes the request through
        # the normal path.
        budget = max(1, min(
            req.params.max_tokens - req.generated,
            self.max_seq_len - 1 - pos,
        ))
        self._import_handoff(handoff_mod.KVHandoff(
            req=req,
            slot=slot,
            position=pos,
            budget=budget,
            pages=tuple(pages),
            nbytes=len(pages) * kv_pages_mod.page_bytes(
                self.model_config.num_layers,
                self.engine_config.page_size,
                self.model_config.num_kv_heads,
                self.model_config.head_dim,
                quantized=self._kv_quant,
                kv_width=self._kv_byte_width,
            ),
            spec_tokens=spec_tokens,
        ))
        with self._lock:
            self._lock.notify_all()
        return "restore"

    def _prefill_wave(
        self,
        admitted: List[_Request],
        register: bool = True,
        between_chunks: Optional[Callable[[], None]] = None,
    ) -> List[object]:
        """Run one claimed wave's prefill mechanics.

        The wave itself was formed by the scheduler policy
        (``SchedulerPolicy.claim_wave``: ONE wave per call, the oldest
        claimable requests up to the row cap, leftover back at the
        queue front; see engine/scheduler/base.py). This method owns
        everything from prefix matching through the chunk walk
        (``_prefill_chunked``: a prompt of at most one chunk is one
        chunk at offset zero) and the radix-cache insert.

        ``register=True`` (the unified policy, dispatch thread)
        registers the finished rows into the decode batch directly —
        the exact pre-scheduler behavior. ``register=False`` (the
        disagg prefill tier) instead returns one
        ``scheduler.handoff.KVHandoff`` record per request, carrying
        the slot/position/budget shadows, the proposer context, and
        the KV pages whose ownership crosses to the decode tier; the
        decode loop registers them in ``_import_handoff``.

        ``between_chunks`` is the policy's: what the dispatch thread
        does between two chunk dispatches of the wave.
        """
        import jax.numpy as jnp

        from generativeaiexamples_tpu.engine.scheduler import handoff as handoff_mod

        records: List[object] = []
        self._wave_entries = []  # stateful prefix entries this wave inserts

        # Prefix-cache matching (a prompt under one chunk is below the
        # smallest cacheable prefix and matches nothing). Hoisted ahead
        # of the paged funding step, which needs each hit's mapped
        # length to size its reservation. Matching pins each hit entry
        # until the funding step's refcount bump secures its pages.
        if self._prefix is not None:
            for req in admitted:
                m = self._prefix.match(
                    req.prompt_ids, hint=req.params.prefix_hint
                )
                if m is not None:
                    req.prefix_entry, req.prefix_len = m
                    flight_recorder.event_rid(
                        req.rid, "prefix_match",
                        cached_tokens=req.prefix_len,
                    )
        # Page funding: reserve every page each request can touch,
        # map prefix hits zero-copy, scatter the page tables to the
        # device. Unfundable claims requeue (OOM backpressure).
        admitted = self._fund_paged_admissions(admitted)
        if not admitted:
            return records

        # The width of the wave's token array: its longest prompt in
        # whole chunks. A dispatch is bounded whatever that is, at
        # Np x prefill_chunk tokens.
        shapes = self.shapes
        bucket = shapes.prefill_bucket(
            max(len(r.prompt_ids) for r in admitted)
        )
        N = len(admitted)
        # Pad up the wave-size ladder (powers of four + num_slots),
        # repeating row 0 — the wave then needs only the shapes
        # warmup() compiles. Coarser than powers of two on purpose:
        # every rung is a separate XLA executable of the whole
        # unrolled walk (~40 s compile each),
        # and at most 3x padding costs far less than it saves.
        # A packed family's wave has ONE row count, the cap: its rows
        # ride one token axis, so the count shapes only the carry.
        cap = shapes.max_wave_rows()
        Np = cap if shapes.packed else min(shapes.wave_pad(N), cap)
        rows = admitted + [admitted[0]] * (Np - N)
        # Per-row cached lengths (prefix hits matched above): warm
        # rows skip their cached chunks in the loop below; the
        # funding step already mapped the shared pages — zero
        # device work.
        cached = None
        if self._prefix is not None:
            cached = np.zeros((Np,), np.int32)
            for i, req in enumerate(rows):
                cached[i] = req.prefix_len
        try:
            tokens = np.zeros((Np, bucket), np.int32)
            lengths = np.zeros((Np,), np.int32)
            slots = np.zeros((Np,), np.int32)
            temps = np.zeros((Np,), np.float32)
            topps = np.zeros((Np,), np.float32)
            seeds = np.zeros((Np,), np.int32)
            for i, req in enumerate(rows):
                T = len(req.prompt_ids)
                tokens[i, :T] = req.prompt_ids
                lengths[i] = T
                slots[i] = req.slot
                temps[i] = req.params.temperature
                topps[i] = req.params.top_p
                seeds[i] = req.sampling_seed & 0x7FFFFFFF
            _M_WAVES.inc()
            first_tokens = self._prefill_chunked(
                tokens, lengths, slots, temps, topps, seeds, cached,
                reqs=admitted, between_chunks=between_chunks,
            )
            # Inject into the device-resident batch state — dispatched, not
            # synced; token values reach the host via the reader.
            # Under the dispatch lock: decode dispatches consume
            # (and rebind) the same slot-state arrays from the
            # decode tier's thread.
            with self._dispatch_lock:
                (
                    self._tokens_dev,
                    self._positions_dev,
                    self._temps_dev,
                    self._topps_dev,
                    self._seeds_dev,
                ) = self._update_slots_fn(
                    self._tokens_dev,
                    self._positions_dev,
                    self._temps_dev,
                    self._topps_dev,
                    self._seeds_dev,
                    jnp.asarray(slots),
                    first_tokens,
                    jnp.asarray(lengths),
                    jnp.asarray(temps),
                    jnp.asarray(topps),
                    jnp.asarray(seeds),
                )
            spec_prop = self._spec_proposer
            first_np = None
            if (
                self._spec_enabled
                and spec_prop is not None
                and any(spec_prop.eligible(r.params) for r in admitted)
            ):
                # Spec proposals need each draft-capable slot's
                # first token on the host BEFORE the next dispatch
                # drafts; sync the wave's first tokens now. Waves
                # with no draft-capable row (e.g. sampled traffic
                # under the lookup proposer) keep the pipelined
                # readback — they never speculate, so the sync
                # would buy nothing.
                # genai-lint: disable=dispatch-readback -- allow-listed spec sync: the next proposal needs this wave's first tokens on the host
                first_np = np.atleast_1d(np.asarray(first_tokens))
            with self._lock:
                for i, req in enumerate(admitted):
                    T = len(req.prompt_ids)
                    req.position = T
                    spec_tokens = None
                    if first_np is not None and spec_prop.eligible(
                        req.params
                    ):
                        spec_tokens = list(req.prompt_ids) + [
                            int(first_np[i])
                        ]
                    # prefill already produced 1 token; the slot can still
                    # need max_tokens - 1 steps (capped by cache capacity).
                    budget = min(
                        req.params.max_tokens - 1, self.max_seq_len - 1 - T
                    )
                    if register:
                        if spec_tokens is not None:
                            self._spec_ctx[req.slot] = spec_tokens
                        self._slot_req[req.slot] = req
                        flight_recorder.event_rid(
                            req.rid, "decode_join", slot=req.slot,
                            position=T,
                        )
                        self._slot_budget[req.slot] = budget
                        self._slot_pos[req.slot] = T
                    else:
                        # Disagg: the decode tier registers at
                        # import; the record carries the shadows
                        # plus the KV pages whose ownership crosses
                        # the tier boundary (refcounts funded at
                        # admission travel with it — no copy).
                        pages = tuple(
                            self._slot_pages.get(req.slot, ())
                        )
                        records.append(handoff_mod.KVHandoff(
                            req=req,
                            slot=req.slot,
                            position=T,
                            budget=budget,
                            pages=pages,
                            nbytes=len(pages) * kv_pages_mod.page_bytes(
                                self._kv_shape.num_layers,
                                self.engine_config.page_size,
                                self._kv_shape.num_kv_heads,
                                self._kv_shape.head_dim,
                                quantized=self._kv_quant,
                                kv_width=self._kv_byte_width,
                            ),
                            spec_tokens=spec_tokens,
                        ))
                self._update_occupancy_gauges()
            if (
                first_np is not None
                and self._draft is not None
                and spec_prop.uses_draft_model
            ):
                # Resident-draft admission: write the wave's
                # prompts into the draft KV cache (chunk-loop of
                # warmed fixed-shape dispatches) and record each
                # drafting slot's frontier at its prompt length —
                # the first spec round's catch-up then feeds just
                # the first token. Device-ordered before any draft
                # proposal for these slots; no sync.
                eligible = np.zeros((len(rows),), bool)
                for i, req in enumerate(admitted):
                    eligible[i] = spec_prop.eligible(req.params)
                # Dispatch lock: the draft cache is donated per
                # dispatch too, and under disagg the decode tier's
                # draft proposals run concurrently with this
                # prefill-tier write.
                with self._dispatch_lock:
                    self._draft.prefill_wave(
                        tokens, lengths, slots, eligible
                    )
                for i, req in enumerate(admitted):
                    if eligible[i]:
                        spec_prop.on_admit(req.slot, int(lengths[i]))
                        flight_recorder.event_rid(
                            req.rid, "draft_prefill",
                            prompt_tokens=int(lengths[i]),
                            spec_proposer=spec_prop.kind,
                        )
        except BaseException as exc:
            # A dispatch failure here (fetch/prefill OOM, compile
            # error) unwinds before _slot_req registration, so the
            # decode-loop error handler can't see these requests:
            # without this unwind their claimed slots would leak
            # from _free_slots forever, their clients would hang to
            # the queue timeout, and any pinned prefix entries
            # would stay refcounted for the process lifetime.
            for ent in self._wave_entries:
                self._prefix.discard(ent)  # a failed admission leaves no entry
            with self._lock:
                for req in admitted:
                    if self._slot_req.get(req.slot) is req:
                        continue  # registered: the loop handler owns it
                    if req.prefix_entry is not None and self._prefix is not None:
                        self._prefix.release(req.prefix_entry)
                        req.prefix_entry = None
                    if req.slot >= 0:
                        pages = self._slot_pages.pop(req.slot, None)
                        self._shared_pages_stale = True
                        if pages:
                            freed = self._kv_alloc.release(pages)
                            self._kv_alloc.observe_request_pages(
                                len(pages)
                            )
                            if req.flight_rec is not None:
                                req.flight_rec.event(
                                    "page_free", rid=req.rid,
                                    pages=len(pages), freed=freed,
                                )
                        self._free_slots.append(req.slot)
                        req.slot = -1
                    if not req.finished:
                        req.error = exc
                        req.finished = True
                        req.out_queue.put(_END)
                        flight_recorder.finish_rid(req.rid, "error")
                self._update_occupancy_gauges()
            raise
        _start_host_copy(first_tokens)
        wave_stats, self._wave_stats = self._wave_stats, []
        self._readback.put((
            "prefill", first_tokens,
            [(i, req) for i, req in enumerate(admitted)], wave_stats,
            self._wave_span,
        ))
        # Insert completed prefills back into the radix cache
        # (dispatch-ordered after the chunk loop; decode only ever
        # appends at positions >= T, never rewriting [0:cached]).
        # Skipped when the prefix is already cached at full depth
        # or every entry ticket is pinned by a live request.
        if self._prefix is not None and not self._state_store_rows:
            for req in admitted:
                # Zero-copy insert: donate the request's own
                # prompt pages (refcount bump) — the entry and
                # the live request share the physical rows; the
                # drop hook releases them on eviction. The
                # request's ongoing decode writes land at
                # positions >= its prompt length, in pages past
                # the chunk-aligned (hence page-aligned) donated
                # span, so donated pages are immutable.
                ent = self._prefix.insert_entry(
                    req.prompt_ids, hint=req.params.prefix_hint
                )
                if ent is not None:
                    self._donate_prefix_pages(req, ent)
        return records

    def _donate_prefix_pages(self, req: _Request, ent) -> None:
        """A new prefix entry takes a reference on the request's own
        prompt pages below the entry's depth."""
        page = self.engine_config.page_size
        # paged_stats() reads this dict from scraper threads under the
        # lock; the donate read takes it too (the PR 7 review pattern).
        with self._lock:
            pages = list(self._slot_pages.get(req.slot, ()))
        donated = pages[: ent.length // page]
        self._kv_alloc.retain(donated)
        ent.pages = list(donated)
        self._shared_pages_stale = True

    def _insert_prefix_state(self, req: _Request) -> None:
        """Between two chunks of ``req``'s admission, right after the one
        that ends at the depth the index names for its prompt: insert
        the entry, donate the pages below that depth and copy the slot's
        fixed state into the entry's store row. The entry can be matched
        once that copy is enqueued; a cancelled request inserts nothing,
        and a wave that fails later drops what it inserted
        (``_prefill_wave``)."""
        if req.cancelled:
            return
        ent = self._prefix.insert_entry(
            req.prompt_ids, hint=req.params.prefix_hint, via=req.prefix_via
        )
        if ent is None:
            return
        self._wave_entries.append(ent)
        self._donate_prefix_pages(req, ent)
        self._copy_prefix_state(
            "save", req, req.slot, self.num_slots + ent.store_slot, ent.length
        )
        self._prefix.mark_ready(ent)

    def _copy_prefix_state(self, what: str, req: _Request, src: int,
                           dst: int, depth: int) -> None:
        """Enqueue the copy of one row of every fixed-state leaf to
        another (``what``: 'save' slot -> store row, 'restore' store row
        -> slot), with its span and counters."""
        import jax.numpy as jnp

        _dtl = self._dtl
        t_wall, t0 = time.time(), time.perf_counter()
        with self._dispatch_lock, self._annotate(f"engine.prefix_state_{what}"):
            t1 = time.perf_counter()
            self._cache, done = self._copy_state_fn(
                self._cache, jnp.asarray(np.array([src, dst], np.int32))
            )
        nbytes = self._state_row_bytes
        store_row = (dst if what == "save" else src) - self.num_slots
        (_M_PREFIX_STATE_SAVES if what == "save"
         else _M_PREFIX_STATE_RESTORES).inc()
        _M_PREFIX_STATE_BYTES.inc(nbytes)
        flight_recorder.event_rid(
            req.rid, f"prefix_state_{what}", store_row=store_row,
            depth_tokens=depth, bytes=nbytes,
        )
        if _dtl is not None:
            _dtl.record_span(
                f"prefix_state_{what}", t_wall=t_wall, lock_wait_s=t1 - t0,
                run_s=time.perf_counter() - t1, rows=1, rids=[req.rid],
                counters={"bytes": nbytes, "store_row": store_row,
                          "depth_tokens": depth},
                handle=done,
            )

    def _import_handoff(self, rec) -> None:
        """Decode-tier import of a prefill-tier handoff (the disagg
        policy's registration step, dispatch thread).

        The KV already sits in the shared pool pages the record lists —
        import is pure host bookkeeping: register the request into the
        decode batch and adopt the slot shadows the prefill tier
        computed. Three edge cases own the rest:

        - the stream already FINISHED (a 1-token request's readback
          outran the import, or an abort was emitted by the reader):
          free the slot and pages here — nothing was registered, so no
          release path would ever fire;
        - the pages went DEAD (defensive — refcounts travel with the
          record, so this means a bug or a future cross-replica
          transport losing a race): requeue for a full re-prefill and
          count it (``genai_engine_handoff_recompute_total`` — the
          gates assert this stays flat);
        - CANCELLED but not yet finished: register normally; the next
          ``_release_finished_slots`` pass emits the end sentinel and
          frees the slot, exactly like a cancelled registered row.
        """
        from generativeaiexamples_tpu.engine.scheduler import handoff as handoff_mod

        req = rec.req
        with self._lock:
            if req.finished:
                if rec.slot >= 0:
                    pages = self._slot_pages.pop(rec.slot, None)
                    self._shared_pages_stale = True
                    if pages:
                        freed = self._kv_alloc.release(pages)
                        self._kv_alloc.observe_request_pages(len(pages))
                        if req.flight_rec is not None:
                            req.flight_rec.event(
                                "page_free", rid=req.rid,
                                pages=len(pages), freed=freed,
                            )
                    self._free_slots.append(rec.slot)
                    req.slot = -1
                if self._spec_proposer is not None:
                    self._spec_proposer.on_release(rec.slot)
                if req.prefix_entry is not None and self._prefix is not None:
                    self._prefix.release(req.prefix_entry)
                    req.prefix_entry = None
                self._update_occupancy_gauges()
                self._lock.notify_all()
                return
            if rec.pages and not self._kv_alloc.all_live(rec.pages):
                handoff_mod.record_recompute()
                logger.error(
                    "handoff import found dead pages for rid %d — "
                    "requeueing for re-prefill (this counter must stay "
                    "flat on the same-host path)", req.rid,
                )
                pages = self._slot_pages.pop(rec.slot, None)
                self._shared_pages_stale = True
                if pages:
                    # Release whatever part of the reservation is still
                    # live — the re-prefill funds a fresh one.
                    live = [
                        p for p in pages if self._kv_alloc.refcount(p) > 0
                    ]
                    if live:
                        self._kv_alloc.release(live)
                if self._spec_proposer is not None:
                    self._spec_proposer.on_release(rec.slot)
                self._free_slots.append(rec.slot)
                req.slot = -1
                req.t_admit = 0.0
                req.prefix_len = 0
                req.prefix_via = None
                self._pending.appendleft(req)
                self._lock.notify_all()
                return
            flight_recorder.event_rid(
                req.rid, "tier_assign", tier="decode", slot=rec.slot
            )
            if rec.spec_tokens is not None:
                self._spec_ctx[rec.slot] = list(rec.spec_tokens)
            self._slot_req[rec.slot] = req
            flight_recorder.event_rid(
                req.rid, "decode_join", slot=rec.slot, position=rec.position
            )
            self._slot_budget[rec.slot] = rec.budget
            self._slot_pos[rec.slot] = rec.position
            self._update_occupancy_gauges()

    def _prefill_chunked(self, tokens, lengths, slots, temps, topps, seeds,
                         cached, reqs, between_chunks=None):
        """Prefill a mixed-length wave as chunk dispatches shaped by
        what each chunk holds.

        Chunk k extends the rows that have tokens at offset k*C by up to
        prefill_chunk of them, and a chunk no row reaches is not
        dispatched. Offsets stay k*C, so only a row's LAST chunk can be
        short and cached prefixes stay chunk- and page-aligned.

        A family with a packed walk (``shapes.packed``) gets the chunk's
        LIVE TOKENS on one axis, row after row, padded up the one token
        ladder (``packed_rungs``): no padded row, no padded width. Any
        other family gets a rectangle: the rows that hold tokens, padded
        up the wave ladder, at the narrowest rung of the width ladder
        that holds the longest (``shapes.chunk_rung`` decides both).

        The per-row last-token hidden accumulates on device over the
        whole wave's rows (the packed program writes each row's into
        the carry itself; a rectangle of fewer rows hands its own back
        through ``_put_rows_fn``); one finish dispatch samples the
        first tokens. Shapes seen by XLA: ``shapes.extend_signatures()``
        — all warmed by warmup(), so no compile can land inside a
        request.

        ``cached`` ([Np] int32, chunk-aligned; None without a prefix store)
        marks each row's prefix
        rows already present in its slot cache (mapped from the prefix
        store at admission): chunks below a row's cached length do not
        hold it, so a warm wave dispatches strictly fewer chunk steps
        than a cold one (cached <= T-1 guarantees every row's final
        chunk still runs, producing its last-token hidden).

        ``reqs`` (the admitted wave, aligned with the first rows of
        ``tokens``; the rows past them are padding, copies of row 0 that
        no chunk computes) feeds the flight recorder one
        ``prefill_chunk`` event per dispatched chunk per live row.

        ``between_chunks`` is called between two chunk dispatches (not
        after the last): the scheduler policy's step there, on this
        thread. The wave's rows are not live yet, and the loop re-reads
        ``self._cache`` inside the dispatch lock, so whatever it
        dispatches joins the one cache chain.
        """
        import jax.numpy as jnp

        shapes = self.shapes
        C = shapes.prefill_chunk
        Np, Tmax = tokens.shape
        n_real = len(reqs)
        K = (Tmax + C - 1) // C
        annotate = self._annotate
        self._wave_stats = []
        self._wave_span = None
        last_h = self._zero_hidden(Np)
        dispatched = 0
        # A family whose prefix entries carry a fixed-state row: a hit's
        # saved state goes into its slot BEFORE its first uncached chunk
        # (which carries the slot's state on, offsets > 0), and the
        # state is saved right after the chunk that ends at the depth
        # the index will name for the prompt. Device order is dispatch
        # order, so each copy sits between the chunks around it.
        save_at: Dict[int, int] = {}
        if self._state_store_rows:
            for i, req in enumerate(reqs):
                if cached[i] > 0 and req.prefix_via is not None:
                    self._copy_prefix_state(
                        "restore", req, self.num_slots + req.prefix_via.store_slot,
                        req.slot, int(cached[i]),
                    )
                depth = self._prefix.cacheable_len(len(req.prompt_ids))
                if depth > cached[i]:
                    save_at[i] = depth
        for k in range(K):
            valid = np.clip(lengths - k * C, 0, C).astype(np.int32)
            if cached is not None:
                valid = np.where(k * C < cached, 0, valid).astype(np.int32)
            valid[n_real:] = 0
            rung = shapes.chunk_rung(valid, n_real)
            if rung is None:
                continue
            live, n, width = rung
            if dispatched and between_chunks is not None:
                between_chunks()
            dispatched += 1
            n_live = len(live)
            W = shapes.extend_window(k, C if shapes.packed else width)
            if shapes.packed:
                # the live rows' tokens one after the other; the rows
                # past them start where the tokens end and hold none
                tok_k = np.zeros((width,), np.int32)
                rows_k = np.zeros((5, Np), np.int32)
                at = 0
                for j, i in enumerate(live):
                    m = int(valid[i])
                    tok_k[at:at + m] = tokens[i, k * C:k * C + m]
                    rows_k[:, j] = (at, m, k * C, slots[i], i)
                    at += m
                rows_k[0, n_live:] = at
                operands = (
                    jnp.asarray(tok_k), jnp.asarray(rows_k),
                    jnp.asarray(np.array(
                        [n_live, shapes.packed_windows().index(W)], np.int32
                    )),
                )
                # (the program places each row in the wave's carry itself;
                # its window is an operand, named above by its index)
                whole, static = True, ()
            else:
                # the whole wave in place where the chunk's rung is the
                # wave's (rows without tokens here ride along dead,
                # their carried hidden kept by the program); else the
                # live rows first, padded with dead copies of themselves
                whole = n == Np
                pick = np.arange(Np) if whole else np.resize(live, n)
                valid_k = valid[pick]
                if not whole:
                    valid_k[n_live:] = 0
                tok_k = np.zeros((n, width), np.int32)
                seg = tokens[pick, k * C:k * C + width]
                tok_k[:, : seg.shape[1]] = seg
                operands = (
                    jnp.asarray(tok_k),
                    jnp.asarray(np.full((n,), k * C, np.int32)),
                    jnp.asarray(valid_k), jnp.asarray(slots[pick]),
                )
                static = (W,)
            # Each _extend_fn call donates the current cache's buffers;
            # read self._cache and rebind INSIDE the dispatch lock so
            # (a) an exception between chunk dispatches never leaves
            # the engine holding deleted donated buffers, and (b) the
            # disagg decode tier's dispatches — which rebind the same
            # cache chain from another thread between chunks — always
            # see a single linear version history. The lock spans only
            # the async enqueue, so decode blocks still interleave
            # with the chunk loop on the device stream (the dispatch-
            # slot contention disagg exists to remove).
            _dtl = self._dtl
            if _dtl is not None:
                _dtl_wall = time.time()
                _dtl_t0 = time.perf_counter()
                _dtl_t1 = _dtl_t0
            with self._dispatch_lock, annotate("engine.prefill_chunk"):
                if _dtl is not None:
                    _dtl_t1 = time.perf_counter()
                sub_h, self._cache, *step_stats = self._extend_fn(
                    self.params,
                    self._cache,
                    *operands,
                    last_h if whole else self._zero_hidden(n),
                    self._tables_dev,
                    *static,
                )
            if whole:
                last_h = sub_h
            else:
                put = np.full((n,), Np, np.int32)
                put[:n_live] = live
                last_h = self._put_rows_fn(last_h, jnp.asarray(put), sub_h)
            live_tokens = int(valid[live].sum())
            _M_EXTEND_COMPUTED.inc(n * width)
            fields = {
                "rows_dispatched": n,
                "width": width,
                "pad_tokens": n * width - live_tokens,
                # live rows that share one token axis (1: nothing packed)
                "packed_rows": n_live if shapes.packed else 1,
            }
            fields.update(self._state_counters(
                "prefill_chunk", n_live, live_tokens,
                n_live * min(k * C, self._span_fields.get("window", 0)),
                resets=n_live if k == 0 else 0,
            ) or {})
            if cached is not None and k and any(cached[i] == k * C for i in live):
                # a row's FIRST uncached chunk after a prefix hit: the
                # offset it read the entry's shared pages from
                fields["prefix_depth_tokens"] = k * C
            if step_stats:
                # the chunk's counts land in its span when the wave's
                # first tokens are read back (_note_stats)
                fields.update(dict.fromkeys(self._stat_names, 0))
                _start_host_copy(step_stats[0])
                self._wave_stats.append((fields, step_stats[0]))
            if _dtl is not None:
                self._wave_span = _dtl.record_span(
                    "prefill_chunk",
                    t_wall=_dtl_wall,
                    lock_wait_s=_dtl_t1 - _dtl_t0,
                    run_s=time.perf_counter() - _dtl_t1,
                    rows=n_live,
                    tokens=live_tokens,
                    rids=[r.rid for r in reqs],
                    counters=fields,
                    handle=sub_h,
                )
            if flight_recorder.enabled():
                for i in live:
                    flight_recorder.event_rid(
                        reqs[i].rid, "prefill_chunk", chunk=k, window=W,
                        tokens=int(valid[i]), width=width,
                    )
            for i in live:
                if save_at.get(i) == (k + 1) * C:
                    self._insert_prefix_state(reqs[i])
        src = np.arange(Np, dtype=np.int32)
        src[n_real:] = 0
        first = self._finish_fn(
            self.params,
            last_h,
            jnp.asarray(src),
            jnp.asarray(lengths),
            jnp.asarray(temps),
            jnp.asarray(topps),
            jnp.asarray(seeds),
        )
        _M_PREFILL_CHUNKS.inc(dispatched)
        return first

    def _zero_hidden(self, rows: int):
        """The zero carry of ``rows`` last-token hidden states, of the
        kind a step program hands on: COMMITTED to the device, as every
        output of a program over the engine's weights is (this one reads
        a row of the embedding and keeps none of it). jit keys an
        executable on whether an operand is committed, so a plain
        ``jnp.zeros`` selects ANOTHER executable of the same program
        than the carry an extend output is: a multi-second load on the
        hot path that no compile counter sees (the compile watch keys
        on shapes; found on the chip in PR 32, four such loads at the
        start of the ramp). Warm-up and serving take every carry from
        here or from an extend output; no program donates it, so one
        array a row count serves for good."""
        carry = self._zero_carries.get(rows)
        if carry is None:
            import jax
            import jax.numpy as jnp

            carry = self._zero_carries[rows] = jax.jit(
                lambda embed: jnp.broadcast_to(
                    jnp.where(False, embed[0], 0), (rows, embed.shape[1])
                )
            )(self.params["embed"])
        return carry

    def _state_counters(self, kind: str, rows: int, tokens: int,
                        ring_tokens: int, resets: int = 0) -> Optional[Dict[str, int]]:
        """Span fields and counters of one launch that the family's
        declarations bring (None for a family that declares none). The
        constant counts of ``span_fields`` go in as they are
        (``kv_readers`` layers that read the one paged K/V,
        ``latent_layers`` latent pools a step reads); a fixed-state
        family adds ``state_rows`` rows whose per-slot state advanced,
        ``window_tokens_read`` ring rows the window layers read (first
        step of a decode block) and, on extend, ``cross_skipped_tokens``:
        tokens the layers past the shared-KV layer never saw (all but
        one a row)."""
        if kind != "decode":
            _M_PREFILL_TOKENS.inc(tokens)  # every family: the skipped share's denominator
        sf = self._span_fields
        # what the derived fields below are computed FROM is no field itself
        fields = {k: v for k, v in sf.items()
                  if k not in ("window", "window_layers", "last_position_only")}
        if not self._fixed_state:
            return fields or None
        fields["state_rows"] = rows
        if "window_layers" in sf:
            fields["window_tokens_read"] = ring_tokens * sf["window_layers"]
        if kind == "decode":
            _M_SSM_DISPATCHES.labels(path="step").inc()
        else:
            _M_SSM_DISPATCHES.labels(path="scan").inc()
            _M_STATE_RESETS.inc(resets)
            if sf.get("last_position_only"):
                # a family whose upper layers see a chunk's last position only
                fields["cross_skipped_tokens"] = max(0, tokens - rows)
                _M_CROSS_SKIPPED.inc(fields["cross_skipped_tokens"])
        return fields

    def _attention_window(self, needed: int) -> int:
        # the one ladder delegate kept: five adapters of the benchmark
        # call it (perfbench/arch/{phi4flash,glm5next,gigachat35,afmoe,solaropen2}.py)
        return self.shapes.attention_window(needed)

    def _spec_has_draftable(self) -> bool:
        """Whether any live row could draft: proposer-eligible (greedy
        for lookup; any non-opted-out row for the draft-model modes)
        and holding a proposer buffer (rows admitted while spec was off
        never draft). When this is False the plain pipelined block path
        serves the batch — spec's per-dispatch host sync buys nothing
        for traffic that cannot speculate."""
        prop = self._spec_proposer
        if prop is None:
            return False
        with self._lock:
            return any(
                slot in self._spec_ctx and prop.eligible(req.params)
                for slot, req in self._slot_req.items()
            )

    def _decode_once(self) -> None:
        # Land any in-flight pipelined verify BEFORE choosing a path:
        # budgets, positions and proposer buffers must be truth even if
        # spec decode was toggled off while the verify was in flight.
        if self._spec_pending is not None:
            self._flush_spec_pipeline()
        if self._spec_enabled and self._spec_has_draftable():
            self._spec_decode_once()
            return
        # Runahead drafts are only consumable by the spec path; a mode
        # switch between rounds drops them (stream-safe: they only ever
        # steered acceptance, never emission).
        self._spec_reconcile = None
        self._step_count += 1
        # Free budget-exhausted and aborted slots BEFORE dispatching so
        # their place goes to pending admissions instead of dead decode
        # steps. The reader still owns emitting budget-exhausted requests'
        # final tokens + _END from the already-dispatched slabs (snapshots
        # pin rows to the old request).
        with self._lock:
            self._release_finished_slots()
            if not self._slot_req:
                return  # everything was budget-exhausted; no live work
            # Smallest power-of-two window covering every query position
            # this block can reach (positions advance by decode_block);
            # the page kernel's one full-capacity program is
            # decode_window's to know.
            window = self.shapes.decode_window(
                max(self._slot_pos.values(), default=0)
            )
            live_slots = list(self._slot_req)
            kv_pages = (
                self._kernel_pages_walked() if self._paged_kernel else None
            )
            state_fields = self._state_counters(
                "decode", len(live_slots), 0,
                sum(
                    min(p + 1, self._span_fields.get("window", 0))
                    for p in self._slot_pos.values()
                ),
            )
            span_counts = dict(
                kv_pages or {}, **(state_fields or {}),
                stream_backlog_tokens=self._stream_backlog_tokens(),
                sampler_full_rows=_sampler_full_rows(self._slot_req.values()),
            )
            # the family's own counts of this dispatch's last step: keys
            # now, values when the slab is read back (_note_stats)
            span_counts.update(dict.fromkeys(self._stat_names, 0))
            # what this block's tokens waited behind: likewise
            span_counts.update(dict.fromkeys(dispatch_timeline_mod.GAP_FIELDS, 0))
            for slot in self._slot_pos:
                self._slot_pos[slot] += self.shapes.decode_block
            self._update_occupancy_gauges()
        # Dispatch lock across read→call→rebind: the disagg prefill
        # tier's chunk dispatches consume/rebind the same donated cache
        # chain and slot-state arrays from its own thread.
        _dtl = self._dtl
        if _dtl is not None:
            _dtl_wall = time.time()
            _dtl_t0 = time.perf_counter()
            _dtl_t1 = _dtl_t0
        with self._dispatch_lock:
            if _dtl is not None:
                _dtl_t1 = time.perf_counter()
            args = (
                self.params,
                self._cache,
                self._tokens_dev,
                self._positions_dev,
                self._temps_dev,
                self._topps_dev,
                self._seeds_dev,
            )
            with self._annotate("engine.decode_block"):
                live = np.zeros((self.num_slots,), bool)
                live[live_slots] = True
                out = self._decode_fn(*args, self._tables_dev, live, window)
            (
                self._tokens_dev,
                self._positions_dev,
                self._cache,
                token_slab,
            ) = out
        _M_DECODE_STEPS.inc(self.shapes.decode_block)
        _M_DECODE_DISPATCHES.inc()
        path = "kernel" if self._paged_kernel else "gather"
        _M_PAGED_ATTN.labels(path=path).inc()
        with self._lock:
            snapshot = list(self._slot_req.items())
            for slot in list(self._slot_budget):
                self._slot_budget[slot] -= self.shapes.decode_block
        span = None
        if _dtl is not None:
            span = _dtl.record_span(
                "decode",
                t_wall=_dtl_wall,
                lock_wait_s=_dtl_t1 - _dtl_t0,
                run_s=time.perf_counter() - _dtl_t1,
                rows=len(live_slots),
                tokens=self.shapes.decode_block * len(live_slots),
                steps=self.shapes.decode_block,
                path=path,
                rids=[r.rid for _, r in snapshot],
                counters=span_counts,
                handle=token_slab,
            )
        # Start the device→host transfer NOW so readbacks overlap both the
        # compute of later steps and each other.
        _start_host_copy(token_slab)
        # Blocks when decode_runahead results await readback — the only
        # backpressure on the dispatch thread.
        self._readback.put(("decode", token_slab, snapshot, span_counts, span))

    def _spec_decode_once(self) -> None:
        """One speculative verify dispatch (prompt-lookup decoding).

        The host drafts up to K tokens per live greedy slot by matching
        the tail of the slot's own prompt+output buffer; the compiled
        verify step scores every draft position for the whole batch in
        ONE dispatch and advances tokens/positions past the accepted
        prefix on device, returning ONE packed [B, K+2] array (verify
        tokens ‖ accepted counts — a single device→host transfer).

        Synchronous mode (``spec_pipeline_enable='off'``, or a proposer
        without runahead support): the dispatch thread SYNCS the packed
        result before returning — the next proposal needs this round's
        emitted tokens — so spec mode trades the decode_runahead
        readback pipeline for multi-token dispatches.

        Pipelined mode ('on' + a runahead-capable proposer): verify N
        is dispatched and LEFT IN FLIGHT — ``copy_to_host_async`` kicks
        the transfer, round N+1's draft is proposed immediately from
        the optimistic full-acceptance context, and the result lands at
        the START of the next dispatch call (_flush_spec_pipeline), so
        emissions, admissions and the next round's host staging all
        overlap the device's verify. The flush either CONFIRMS the
        optimistic draft (acceptance matched the assumption — round
        N+1 dispatches with zero proposal work on the critical path) or
        ROLLS IT BACK to a fresh proposal from the true buffers. Either
        way the draft only ever steers acceptance — emission comes from
        the verify outputs — so streams are token-identical across
        pipeline on/off and spec on/off."""
        import jax.numpy as jnp

        # Consume the runahead reconcile the flush (already run by
        # _decode_once) left for us, if any.
        reconcile = self._spec_reconcile
        self._spec_reconcile = None
        self._step_count += 1
        K = self._spec_draft
        ak = self._adaptive_k
        if ak is not None:
            # Acceptance-adaptive width: this round's verify width from
            # the scheduler's rolling acceptance window. Every rung is
            # a warmed executable (warmup_spec_shapes walks the closed
            # ladder) and funding stayed at the configured max K, so
            # the pick only narrows the dispatch, never the reservation.
            K = ak.pick(self.scheduler.tracker.ratio())
        with self._lock:
            # Eager budget/abort releases, exactly as the block path does.
            self._release_finished_slots()
            if not self._slot_req:
                return
            max_pos_live = max(self._slot_pos.values(), default=0)
            # The verify chunk writes K+1 rows past each live position,
            # so the window must cover the accepted frontier plus the
            # full draft width (the per-row accepted length is only
            # known after the dispatch). The ragged verify kernel
            # tracks lengths itself — one full-capacity executable.
            if self._paged_verify_kernel:
                window = self.max_seq_len
            else:
                window = self.shapes.attention_window(
                    min(max_pos_live + K + 1, self.max_seq_len)
                )
            live = np.zeros((self.num_slots,), bool)
            snapshot = list(self._slot_req.items())
            caps = {
                slot: spec_decode_mod.cap_draft_len(
                    K, self._slot_pos[slot], self._slot_budget[slot],
                    self.max_seq_len,
                )
                for slot, _ in snapshot
            }
        # Proposals run OUTSIDE the lock: the per-slot buffers are
        # single-writer (this thread), and the proposer's work (n-gram
        # scans, or the batched draft-model dispatch + its sync) must
        # never block submit() or the reader's emissions.
        prop = self._spec_proposer
        # Draft-aware scheduling (scheduler policy seam, ROADMAP 4c):
        # when the rolling acceptance ratio collapsed below
        # spec_draft_min_acceptance, skip the resident-draft dispatch
        # for this wave — the synced block fallback keeps the proposer
        # buffers exact, so periodic probe rounds can re-measure and a
        # recovered workload resumes drafting. Lookup proposals are
        # host-side n-gram scans (near-free) and never gate.
        if prop.uses_draft_model and not self.scheduler.should_draft():
            for slot, _ in snapshot:
                live[slot] = True
            self._spec_block_fallback(snapshot, live, max_pos_live)
            return
        pipelined = self._spec_pipeline and prop.supports_runahead
        draft, draft_len = self._spec_stage_arrays(K)
        prop_rows = []
        for slot, req in snapshot:
            live[slot] = True
            if not prop.eligible(req.params):
                continue  # single-token row inside the same dispatch
            # genai-lint: disable=lock-discipline -- single-writer: only this dispatch thread mutates _spec_ctx entries, and _release (the other mutator) runs on this same thread
            ctx = self._spec_ctx.get(slot)
            if not ctx:
                continue  # admitted while spec was off: never drafts
            prop_rows.append((slot, ctx, caps[slot]))
        proposals = self._spec_propose(prop, prop_rows, reconcile)
        for slot, d in proposals.items():
            if d:
                draft[slot, : len(d)] = d
                draft_len[slot] = len(d)
        if not draft_len.any():
            # No row drafted (sampled-only wave, opted-out rows, or no
            # n-gram matches): a 1-token verify would forfeit the
            # decode_block fusion for nothing, so run the plain fused
            # block program instead — synced here (not via the runahead
            # pipeline) to keep the proposer buffers exact.
            self._spec_block_fallback(snapshot, live, max_pos_live)
            return
        if ak is not None:
            # Only rounds that actually dispatch a verify count toward
            # effective_k_mean (fallback rounds run the plain block).
            spec_decode_mod.record_adaptive_round(K)
        # Host→device staging OUTSIDE the dispatch lock (lock
        # narrowing): the copies read the double-buffered host arrays,
        # which nothing else touches, so the lock need only cover the
        # enqueue + rebind window it was built for.
        draft_dev = jnp.asarray(draft)
        draft_len_dev = jnp.asarray(draft_len)
        _dtl = self._dtl
        if _dtl is not None:
            _dtl_wall = time.time()
            _dtl_t0 = time.perf_counter()
            _dtl_t1 = _dtl_t0
        with self._dispatch_lock, self._annotate("engine.spec_verify"):
            if _dtl is not None:
                _dtl_t1 = time.perf_counter()
            spec_args = (
                self.params,
                self._cache,
                self._tokens_dev,
                self._positions_dev,
                self._temps_dev,
                self._topps_dev,
                self._seeds_dev,
                draft_dev,
                draft_len_dev,
                live,
            )
            out = self._spec_verify_fn(*spec_args, self._tables_dev, window)
            (
                self._tokens_dev,
                self._positions_dev,
                self._cache,
                packed,
            ) = out
        span = None
        if _dtl is not None:
            # recorded at its enqueue; its token count lands with the
            # readback (_spec_apply_readback)
            span = _dtl.record_span(
                "spec",
                t_wall=_dtl_wall,
                lock_wait_s=_dtl_t1 - _dtl_t0,
                run_s=time.perf_counter() - _dtl_t1,
                rows=len(snapshot),
                path="kernel" if self._paged_verify_kernel else "gather",
                rids=[r.rid for _, r in snapshot],
                counters=dict.fromkeys(_dtl.GAP_FIELDS, 0),
                handle=packed,
            )
        _M_DECODE_STEPS.inc(1)
        _M_DECODE_DISPATCHES.inc()
        _sampler_full_rows(r for _, r in snapshot)
        _M_PAGED_ATTN.labels(
            path="kernel" if self._paged_verify_kernel else "gather"
        ).inc()
        if pipelined:
            # Leave verify N in flight: kick the device→host transfer,
            # then spend the device's compute time drafting round N+1
            # under the full-acceptance assumption. The next dispatch
            # call lands the result (_flush_spec_pipeline) and either
            # confirms this runahead draft or rolls it back.
            _start_host_copy(packed)
            self._spec_pending = {
                "packed": packed,
                "snapshot": snapshot,
                "draft_len": draft_len,
                "prop_kind": prop.kind,
                "span": span,
                "opt": self._spec_runahead_proposals(
                    prop, prop_rows, proposals, K
                ),
            }
            return
        # The sole sync in spec mode (dispatch thread): proposer buffers
        # must reflect this dispatch before the next one drafts. ONE
        # packed fetch (tokens ‖ accepted) where two back-to-back syncs
        # used to serialize; the reader still gets pre-fetched host
        # values, so emission, stop handling and metrics stay in one
        # place.
        t0 = time.time()
        # genai-lint: disable=dispatch-readback -- allow-listed spec-verify sync: proposer buffers must reflect this dispatch before the next one drafts (the prompt-lookup bargain; one packed tokens‖accepted fetch)
        packed_np = np.asarray(packed)
        readback_s = time.time() - t0
        out_np = packed_np[:, :-1]
        acc_np = packed_np[:, -1]
        _M_READBACK.labels(kind="spec").observe(readback_s, trace_id=None)
        if _dtl is not None:
            _dtl.record_readback("spec", readback_s)
        self._spec_apply_readback(
            out_np, acc_np, snapshot, draft_len, prop.kind, span
        )

    def _flush_spec_pipeline(self) -> None:
        """Land the in-flight pipelined verify: sync the packed result
        (the async transfer was kicked at dispatch, so this waits only
        for whatever the overlapped host work did not cover), apply the
        truth updates one round late, and reconcile the optimistic
        runahead draft against the actual acceptance — leaving a
        (confirmed, missed) record for the next spec round. Runs at the
        top of every dispatch call and at shutdown; callers that are
        not the spec path simply drop the reconcile."""
        pending = self._spec_pending
        self._spec_pending = None
        self._spec_reconcile = None
        if pending is None:
            return
        snapshot = pending["snapshot"]
        t0 = time.time()
        # genai-lint: disable=dispatch-readback -- allow-listed pipeline flush: the ONE sync of the pipelined spec path, one dispatch round after its verify was enqueued
        packed_np = np.asarray(pending["packed"])
        wait_s = time.time() - t0
        out_np = packed_np[:, :-1]
        acc_np = packed_np[:, -1]
        _M_READBACK.labels(kind="spec").observe(wait_s, trace_id=None)
        _dtl = self._dtl
        if _dtl is not None:
            _dtl.record_readback("spec", wait_s)
            _dtl.record_pipeline_flush(wait_s, rows=len(snapshot))
        self._spec_apply_readback(
            out_np, acc_np, snapshot, pending["draft_len"],
            pending["prop_kind"], pending["span"],
        )
        # Reconcile the runahead drafts: the optimistic context assumed
        # FULL acceptance, and its first proposed token doubles as the
        # runahead's prediction of the bonus token — so one acceptance
        # count plus one token comparison decides each row.
        opt = pending["opt"]
        if not opt:
            return
        forced = False
        try:
            faults_mod.fault_point("engine.spec_pipeline")
        except faults_mod.FaultInjected:
            forced = True  # test hook: invalidate every runahead draft
        confirmed: Dict[int, List[int]] = {}
        missed = set()
        for slot, (dlen, od) in opt.items():
            acc = int(acc_np[slot])
            if (
                not forced
                and acc == dlen
                and od
                and od[0] == int(out_np[slot, acc])
            ):
                if len(od) > 1:
                    confirmed[slot] = od[1:]
                else:
                    # The runahead draft spent itself predicting the
                    # bonus token — nothing left to dispatch, nothing
                    # to roll back; the next round proposes fresh. The
                    # optimism was still VALIDATED, so it counts toward
                    # confirmed here (consumable drafts count at
                    # consumption, in _spec_propose) — otherwise the
                    # rollback rate overstates on 1-token-draft phases.
                    _M_SPEC_PIPE_CONFIRMED.inc()
            else:
                missed.add(slot)
        self._spec_reconcile = (confirmed, missed)

    def _spec_apply_readback(
        self, out_np, acc_np, snapshot, draft_len, prop_kind, span=None
    ) -> None:
        """Apply a landed verify readback: acceptance telemetry, the
        scheduler's rolling-acceptance feed, budget/position shadows,
        proposer buffers, and the reader handoff. Shared by the
        synchronous path (right after its sync) and the pipeline flush
        (one round later). The ``is req`` slot guards make the
        late-flush case safe against a row that was released — and
        possibly re-admitted — while the verify was in flight."""
        if span is not None:
            span.tokens = sum(int(acc_np[s]) + 1 for s, _ in snapshot)
        # Rolling-acceptance feed for draft-aware scheduling (the
        # policy's tracker; zero-draft rounds carry no evidence).
        self.scheduler.record_spec_round(
            int(draft_len.sum()), sum(int(acc_np[s]) for s, _ in snapshot)
        )
        with self._lock:
            for slot, req in snapshot:
                n = int(acc_np[slot]) + 1
                spec_decode_mod.record_dispatch(int(draft_len[slot]), n - 1)
                if self._slot_req.get(slot) is not req:
                    continue  # released (or recycled) mid-flight
                if int(draft_len[slot]):
                    flight_recorder.event_rid(
                        req.rid, "spec_verify",
                        drafted=int(draft_len[slot]), accepted=n - 1,
                        spec_proposer=prop_kind,
                    )
                if slot in self._slot_budget:
                    self._slot_budget[slot] -= n
                if slot in self._slot_pos:
                    self._slot_pos[slot] = min(
                        self._slot_pos[slot] + n, self.max_seq_len - 1
                    )
                buf = self._spec_ctx.get(slot)
                if buf is not None:
                    buf.extend(int(t) for t in out_np[slot, :n])
            self._update_occupancy_gauges()
        # put() outside the lock (the reader needs it inside _emit)
        self._readback.put(("spec", (out_np, acc_np), snapshot, span))

    def _spec_propose(self, prop, prop_rows, reconcile):
        """This round's drafts: consume confirmed runahead drafts
        (proposed while the previous verify ran — zero host work now),
        re-propose rolled-back rows from the true buffers, and propose
        fresh for rows the runahead had nothing for."""
        def _wave(rows):
            if not rows:
                return {}
            # Dispatch lock around the proposal (the draft-model
            # proposers dispatch against the donated draft cache; the
            # disagg prefill tier writes the same cache at admission).
            if prop.uses_draft_model:
                with self._dispatch_lock:
                    return prop.propose_wave(rows)
            return prop.propose_wave(rows)

        if reconcile is None:
            return _wave(prop_rows)
        confirmed, missed = reconcile
        proposals: Dict[int, List[int]] = {}
        fresh = []
        rolled = 0
        t0 = time.perf_counter()
        for slot, ctx, cap in prop_rows:
            d = confirmed.get(slot)
            if d is not None:
                d = d[:cap]
                if d:
                    proposals[slot] = d
                    _M_SPEC_PIPE_CONFIRMED.inc()
                    continue
            if slot in missed:
                rolled += 1
            fresh.append((slot, ctx, cap))
        proposals.update(_wave(fresh))
        if rolled:
            _M_SPEC_PIPE_ROLLBACKS.inc(rolled)
            if self._dtl is not None:
                # The re-proposal work the rollback put back on the
                # critical path (the fresh wave includes never-drafted
                # rows too; the split is not worth a second wave).
                self._dtl.record_rollback(
                    time.perf_counter() - t0, rows=rolled
                )
        return proposals

    def _spec_runahead_proposals(self, prop, prop_rows, proposals, K):
        """Draft round N+1 while verify N runs on device, assuming FULL
        acceptance of the just-dispatched draft: the optimistic context
        is the true buffer plus the whole draft (list concat — the
        per-slot buffers are never mutated here), and the optimistic
        cap assumes the bonus token landed too. The first optimistic
        token doubles as the runahead's prediction of that bonus token,
        so the flush confirms with a single comparison. A wrong guess
        costs only this host work — which ran inside device time
        anyway."""
        opt_rows = []
        opt_dlen = {}
        with self._lock:
            pos = dict(self._slot_pos)
            budget = dict(self._slot_budget)
        for slot, ctx, _cap in prop_rows:
            d = proposals.get(slot) or []
            dlen = len(d)
            opt_cap = spec_decode_mod.cap_draft_len(
                K,
                min(pos.get(slot, 0) + dlen + 1, self.max_seq_len - 1),
                budget.get(slot, 0) - (dlen + 1),
                self.max_seq_len,
            )
            if opt_cap < 1:
                continue  # the row ends (or nearly ends) this round
            opt_rows.append((slot, ctx + d, opt_cap))
            opt_dlen[slot] = dlen
        if not opt_rows:
            return {}
        od = prop.propose_wave(opt_rows)
        return {
            slot: (opt_dlen[slot], od.get(slot) or [])
            for slot in opt_dlen
        }

    def _spec_stage_arrays(self, K: int):
        """Pre-staged host arrays for the verify draft inputs,
        double-buffered: generation N+1 fills one buffer while
        generation N's may still back an in-flight host→device copy
        (and its draft_len feeds the deferred flush). Runahead depth is
        1, so two generations suffice; the flush of round N always runs
        before round N+2 reclaims N's buffer."""
        stage = self._spec_stage
        if stage is None or stage[0][0][0].shape != (self.num_slots, K):
            stage = self._spec_stage = (
                [
                    (
                        np.zeros((self.num_slots, K), np.int32),
                        np.zeros((self.num_slots,), np.int32),
                    ),
                    (
                        np.zeros((self.num_slots, K), np.int32),
                        np.zeros((self.num_slots,), np.int32),
                    ),
                ],
                [0],
            )
        bufs, idx = stage
        draft, draft_len = bufs[idx[0]]
        idx[0] = 1 - idx[0]
        draft[:] = 0
        draft_len[:] = 0
        return draft, draft_len

    def _spec_block_fallback(self, snapshot, live, max_pos_live) -> None:
        """One fused block-decode dispatch from inside spec mode, used
        when no live row produced a draft. Emits decode_block tokens per
        row like the plain path, but SYNCS the slab on this thread so
        the proposer buffers (and budget/position shadows) stay exact —
        the next dispatch may draft again. The reader receives the
        pre-fetched slab under its own "spec_block" kind, so the host
        values do not inject bogus ~0 s samples into the decode
        readback histogram."""
        window = self.shapes.decode_window(max_pos_live)
        _dtl = self._dtl
        if _dtl is not None:
            _dtl_wall = time.time()
            _dtl_t0 = time.perf_counter()
            _dtl_t1 = _dtl_t0
        with self._dispatch_lock:
            if _dtl is not None:
                _dtl_t1 = time.perf_counter()
            args = (
                self.params,
                self._cache,
                self._tokens_dev,
                self._positions_dev,
                self._temps_dev,
                self._topps_dev,
                self._seeds_dev,
            )
            with self._annotate("engine.decode_block"):
                out = self._decode_fn(*args, self._tables_dev, live, window)
                (
                    self._tokens_dev,
                    self._positions_dev,
                    self._cache,
                    token_slab,
                ) = out
        span = None
        if _dtl is not None:
            span = _dtl.record_span(
                "spec_block",
                t_wall=_dtl_wall,
                lock_wait_s=_dtl_t1 - _dtl_t0,
                run_s=time.perf_counter() - _dtl_t1,
                rows=len(snapshot),
                tokens=self.shapes.decode_block * len(snapshot),
                steps=self.shapes.decode_block,
                path="kernel" if self._paged_kernel else "gather",
                rids=[r.rid for _, r in snapshot],
                counters=dict.fromkeys(_dtl.GAP_FIELDS, 0),
                handle=token_slab,
            )
        _M_DECODE_STEPS.inc(self.shapes.decode_block)
        _M_DECODE_DISPATCHES.inc()
        _sampler_full_rows(r for _, r in snapshot)
        path = "kernel" if self._paged_kernel else "gather"
        _M_PAGED_ATTN.labels(path=path).inc()
        t0 = time.time()
        # genai-lint: disable=dispatch-readback -- allow-listed spec-block sync: the zero-draft fallback slab feeds the proposer buffers, so it must land before the next dispatch
        slab_np = np.asarray(token_slab)  # [block, batch]
        _M_READBACK.labels(kind="spec_block").observe(
            time.time() - t0, trace_id=None
        )
        if _dtl is not None:
            _dtl.record_readback("spec_block", time.time() - t0)
        with self._lock:
            for slot, req in snapshot:
                if slot in self._slot_budget:
                    self._slot_budget[slot] -= self.shapes.decode_block
                if slot in self._slot_pos:
                    self._slot_pos[slot] += self.shapes.decode_block
                buf = self._spec_ctx.get(slot)
                if buf is not None:
                    buf.extend(int(t) for t in slab_np[:, slot])
            self._update_occupancy_gauges()
        self._readback.put(("spec_block", slab_np, snapshot, span))

    def warmup_spec_shapes(self) -> None:
        """Compile the spec verify executable at every attention-window
        rung (static ``window`` arg — one XLA program each, ~40 s per
        compile on a TPU). Zero-live dispatches are
        value-level no-ops on the caches, so no scheduler involvement is
        needed — but the caches are DONATED, so live decode must quiesce
        first (same discipline as warmup()). Called by
        warmup() when spec is enabled and by runtime-toggle callers;
        without it the first verify dispatch at each window rung would
        compile inside a request."""
        if not self._spec_available:
            return
        import jax.numpy as jnp

        # The ragged verify kernel runs at one full-capacity window
        # (lengths come from the prefetched tables) — a single
        # executable to warm instead of the whole rung ladder.
        if self._paged_verify_kernel:
            windows = [self.max_seq_len]
        else:
            windows = self.shapes.window_rungs()
        with self._compile_watch.warmup_scope(), self.hold_admissions():
            if not self._quiesce_for_warmup("warmup_spec_shapes"):
                return
            B = self.num_slots
            zeros_i = jnp.zeros((B,), jnp.int32)
            live = np.zeros((B,), bool)
            # The verify program is shape-polymorphic over the draft
            # width, so adaptive K multiplies the warm set by its
            # ladder: one executable per (window rung, K rung) keeps
            # every width the acceptance trajectory can pick warmed
            # (the closed-ladder contract — hot-path compiles stay 0).
            if self._adaptive_k is not None:
                k_rungs = self._adaptive_k.ladder
            else:
                k_rungs = (self._spec_draft,)
            for w in windows:
                for kr in k_rungs:
                    draft = jnp.zeros((B, kr), jnp.int32)
                    # on the slot arrays themselves, as serving runs it
                    # (jit keys an executable on an operand's kind); all
                    # rows dead, the outputs dropped — only the caches
                    # are donated and must be rebound from the output
                    (_, _, self._cache, packed) = self._spec_verify_fn(
                        self.params, self._cache, self._tokens_dev,
                        self._positions_dev, self._temps_dev,
                        self._topps_dev, self._seeds_dev, draft, zeros_i,
                        live, self._tables_dev, w,
                    )
                    packed.block_until_ready()
            if self._draft is not None:
                # Resident-draft executables (draft_prefill per
                # (row rung, chunk window), draft_propose per window
                # rung) compile in the same warmup scope — the loadgen
                # hot-path gate stays at zero with the draft resident.
                self._draft.warmup()

    def set_spec_decode(self, enabled: bool) -> bool:
        """Toggle prompt-lookup speculative decoding at runtime (A/B
        runs, tests). Returns the effective state — False when the model
        family has no verify program. Safe while
        serving: the flag only picks which compiled program the NEXT
        decode dispatch runs; rows admitted while spec was off have no
        token buffer and simply never draft until their slot recycles."""
        with self._lock:
            self._spec_enabled = bool(enabled) and self._spec_available
            if not self._spec_enabled:
                # Buffers stop tracking emissions under block decode;
                # drop them so a later re-enable starts from fresh
                # admissions instead of stale tails (stale drafts are
                # safe — verify rejects them — but pure waste). The
                # draft frontiers follow the buffers (same staleness).
                self._spec_ctx.clear()
                if self._spec_proposer is not None:
                    self._spec_proposer.reset()
                # Runahead drafts are keyed to the dropped buffers; any
                # in-flight verify still lands via the flush (its slot
                # guards skip recycled rows).
                self._spec_reconcile = None
            return self._spec_enabled

    def set_spec_proposer(self, kind: str) -> Optional[str]:
        """Switch the draft proposer at runtime (A/B runs, tests). Returns the effective kind, or None when this serving
        path has no verify program or the draft-model runtime cannot be
        built (no ``spec_draft_model`` configured). Building the
        runtime lazily compiles the draft programs — callers should
        re-run :meth:`warmup_spec_shapes` before measuring. Safe while
        serving for the same reason ``set_spec_decode`` is: the
        proposer only shapes the NEXT dispatch's drafts, and rows keep
        (or newly gain) their buffers at the following admission."""
        if not self._spec_available:
            return None
        cfg = self.engine_config
        if kind == "lookup":
            prop = spec_decode_mod.LookupProposer(self._spec_ngram)
        elif kind in ("draft_model", "combined"):
            if self._draft is None:
                if not (cfg.spec_draft_model or cfg.spec_draft_checkpoint_path):
                    return None
                self._draft = self._build_draft_runtime(cfg)
            if kind == "draft_model":
                prop = spec_decode_mod.DraftModelProposer(self._draft)
            else:
                prop = spec_decode_mod.CombinedProposer(
                    self._spec_ngram, self._draft
                )
        else:
            raise ValueError(
                f"spec proposer must be one of "
                f"{'|'.join(spec_decode_mod.PROPOSER_KINDS)}, got {kind!r}"
            )
        with self._lock:
            # Frontier/buffer state keyed to the OLD proposer's
            # eligibility rule goes stale on a switch; drop both so the
            # next admissions rebuild them consistently.
            if self._spec_proposer is not None:
                self._spec_proposer.reset()
            self._spec_ctx.clear()
            self._spec_reconcile = None  # drafts from the old proposer
            self._spec_proposer = prop
        return prop.kind

    # ------------------------------------------------------------------ //
    # reader loop: the sole device→host synchronization point.
    def _reader_loop(self) -> None:
        while True:
            item = self._readback.get()
            if item is None:
                with self._lock:
                    for slot, req in list(self._slot_req.items()):
                        if not req.finished:
                            req.finished = True
                            req.out_queue.put(_END)
                            flight_recorder.finish_rid(req.rid, "shutdown")
                return
            kind, handle, slots, *extra = item
            if kind == "spec":
                # Verify results arrive pre-fetched (the dispatch thread
                # synced them for its proposer buffers): emit each row's
                # accepted tokens + bonus through the same stop/metrics
                # path as plain decode. Rows past their stop are skipped
                # token-by-token, exactly like slab overrun.
                out_np, acc_np = handle
                self._hand_off(
                    ((req, out_np[slot, : int(acc_np[slot]) + 1])
                     for slot, req in slots),
                    *extra,
                )
                continue
            if kind == "spec_block":
                # Zero-draft fallback slab, pre-fetched by the dispatch
                # thread (which observed the real wait under
                # kind="spec_block"): emit like a decode slab without
                # injecting a bogus ~0 s decode-readback sample.
                self._emit_slab(np.asarray(handle), slots, *extra)
                continue
            if kind == "drain_barrier":
                # Drain quiesce point (the drain thread enqueues this
                # FIFO-last, after the dispatch loop parks): every
                # earlier slab/prefill readback has been emitted, so
                # req.position and req.emitted are current when the
                # waiter wakes.
                handle.set()
                continue
            try:
                t0 = time.time()
                values = np.asarray(handle)  # sync: blocks until the device is done
                # Per-kind device-completion waits: how long the reader
                # stalled for this dispatch to finish — the on-line view
                # of where serving time goes (prefill waves vs decode
                # blocks) without a profiler attach.
                _M_READBACK.labels(kind=kind).observe(
                    time.time() - t0, trace_id=None
                )
                if self._dtl is not None:
                    self._dtl.record_readback(kind, time.time() - t0)
            except Exception as exc:  # noqa: BLE001
                logger.exception("readback error: %s", exc)
                for _, req in slots:
                    if not req.finished:
                        req.error = exc
                        req.finished = True
                        req.out_queue.put(_END)
                        flight_recorder.finish_rid(req.rid, "error")
                continue
            if kind == "prefill":
                values = np.atleast_1d(values)
                # a first token is a hand-off too: it starts its row's
                # first gap, after the stamp of the wave's last chunk
                self._hand_off(
                    ((req, values[row : row + 1]) for row, req in slots),
                    *extra[1:], advance=False,
                )
                # the wave's chunks ran before its first tokens: their
                # counts are on the host already
                for fields, stats in (extra[0] if extra else ()):
                    self._note_stats(fields, np.asarray(stats))
                continue
            if self._stat_names and extra:
                block = self.shapes.decode_block
                self._note_stats(extra[0], values[block:].reshape(-1))
                values = values[:block]
            self._emit_slab(values, slots, *extra[1:])

    def _note_stats(self, fields: Dict[str, int], values: np.ndarray) -> None:
        """A family's counts of one dispatch (models/registry.py
        ``stat_names``), read back with its tokens: into the span's
        fields (whose keys the dispatch wrote, so the dict keeps its
        size under a concurrent scrape) and into the counters that
        carry a stat's name."""
        for name, value in zip(self._stat_names, values.tolist()):
            fields[name] = int(value)
            counter = _STAT_COUNTERS.get(name)
            if counter is not None:
                counter.inc(int(value))

    def _hand_off(self, rows, span=None, advance: bool = True) -> None:
        """One readback's tokens, request by request: ``rows`` yields
        (request, its tokens); a request that finished in an earlier
        readback overran past its stop and is skipped. With the timeline
        on, what each row's gap since its previous hand-off was made of
        is split against the device clock, read once the launch being
        read back (``span``) is stamped, and the longest gap's parts land
        in that span's fields (engine/dispatch_timeline.py
        ``HandoffBlock``)."""
        gaps = None if self._dtl is None else self._dtl.HandoffBlock(span)
        for req, tokens in rows:
            if not req.finished:
                self._emit(req, tokens, advance, gaps)
        if gaps is not None:
            gaps.close()

    def _emit_slab(self, slab: np.ndarray, slots, span=None) -> None:
        """A decode slab ``[block, batch]``, oldest step first, walked
        request by request: a row's tokens of one slab reach its stream
        in ONE put."""
        self._hand_off(((req, slab[:, slot]) for slot, req in slots), span)

    def _emit(self, req: _Request, tokens: np.ndarray, advance: bool = True,
              gaps=None) -> None:
        """Reader-thread accounting of one request's tokens of one
        readback, in order: each counted as before (position, stop ids,
        ``max_tokens``, the latency histograms), all handed to the
        stream in one put; queues _END + frees the slot. Tokens past the
        one that ends the request are dropped. ``advance=False``: a
        prefill's first token, whose position the admission counted.
        The tokens arrive at ONE instant: the wall clock is read once a
        call, and ``gaps`` (the readback's ``HandoffBlock``) splits the
        gap since the request's previous hand-off."""
        stop_ids = _NO_STOP_IDS if req.params.ignore_eos else self._stop_ids
        block: List[Optional[int]] = []
        done = False
        now = time.time()
        last = req.t_last_token
        if gaps is not None:
            gaps.handoff(req, now)
        req.t_last_token = now
        for token in tokens.tolist():
            if advance:
                req.position += 1
            req.generated += 1
            req.emitted.append(token)
            if req.generated == 1 and req.t_submit:
                ttft = now - req.t_submit
                _M_TTFT.observe(ttft, trace_id=req.trace_hex)
                _M_PREFILL_WAIT.observe(
                    now - (req.t_admit or req.t_submit), trace_id=req.trace_hex
                )
                slo_mod.observe_latency("ttft_p95", ttft)
                flight_recorder.event_rid(
                    req.rid, "first_token", ttft_s=round(ttft, 6)
                )
            elif last:
                itl = now - last
                _M_TOKEN_LATENCY.observe(itl, trace_id=req.trace_hex)
                slo_mod.observe_latency("inter_token_p95", itl)
            last = now
            done = (
                token in stop_ids
                or req.generated >= req.params.max_tokens
                or req.position >= self.max_seq_len - 1
                or req.cancelled
            )
            if token not in stop_ids:
                block.append(token)
            if done:
                break
        req.queued += len(block)
        _M_TOKENS.inc(len(block) + (done and token in stop_ids))
        if done:
            req.finished = True
            block.append(_END)
        if block:
            # the put's time rides along only where the gap is split too
            req.out_queue.put_many(block, now if gaps is not None else 0.0)
        if done:
            # The reader's own count and stop reason: the eager
            # decode_leave event fires at dispatch time, before the
            # reader has counted the block's tokens.
            # ``generated`` here is what the stream DELIVERED: the stop
            # id that ends an answer is sampled and counted in
            # req.generated (positions follow it) but is never a frame.
            flight_recorder.finish_rid(
                req.rid, "abort" if req.cancelled else "finish",
                generated=req.generated - (token in stop_ids),
                stop=(
                    "eos" if token in stop_ids
                    else "max_tokens" if req.generated >= req.params.max_tokens
                    else "capacity" if req.position >= self.max_seq_len - 1
                    else "abort"
                ),
            )
            if req.slot >= 0:
                self._release_q.put((req.slot, req))
                with self._lock:
                    self._lock.notify_all()

    def _release_finished_slots(self) -> None:
        """Eager dispatch-thread releases (caller holds the lock):
        budget-exhausted slots and aborted/cancelled requests free their
        slot (and prefix pins, via _release) before the next dispatch.
        Cancelled requests also get their end sentinel here — once the
        slot is recycled no future readback will finish them."""
        for slot in list(self._slot_budget):
            req = self._slot_req.get(slot)
            budget_done = self._slot_budget.get(slot, 1) <= 0
            cancelled = req is not None and req.cancelled
            if not budget_done and not cancelled:
                continue
            if cancelled and not req.finished:
                req.finished = True
                req.out_queue.put(_END)
                flight_recorder.finish_rid(req.rid, "abort")
            self._release(slot, req)

    def _release(self, slot: int, req: Optional[_Request]) -> None:
        """Dispatch-thread slot recycling (caller holds the lock).

        The slot is freed only while it still belongs to ``req``: after an
        eager (budget-exhausted) release re-assigns the slot, the reader's
        late release for the old request must not yank it from the new one.
        """
        if req is not None and self._slot_req.get(slot) is req:
            self._slot_req.pop(slot)
            self._slot_budget.pop(slot, None)
            self._slot_pos.pop(slot, None)
            self._spec_ctx.pop(slot, None)
            if self._spec_proposer is not None:
                # Draft-KV frontier bookkeeping dies with the slot (the
                # draft cache rows themselves need no scrub — admission
                # re-prefills a recycled slot's strip from position 0).
                self._spec_proposer.on_release(slot)
            self._free_slots.append(slot)
            # Drop the request's page reservation: shared prefix
            # pages keep their cache-entry refcount; exclusively
            # owned pages return to the free list. In-flight
            # dispatches for this slot run with live=False and
            # write only the scratch page, so re-issued pages are
            # safe immediately.
            pages = self._slot_pages.pop(slot, None)
            self._shared_pages_stale = True
            if pages is not None:
                freed = self._kv_alloc.release(pages)
                self._kv_alloc.observe_request_pages(len(pages))
                if req.flight_rec is not None:
                    # directly on the record: the rid unmapped when
                    # the stream finished, but the free happens now
                    req.flight_rec.event(
                        "page_free", rid=req.rid,
                        pages=len(pages), freed=freed,
                    )
            flight_recorder.event_rid(
                req.rid, "decode_leave", slot=slot, generated=req.generated
            )
            if not self._slot_req:
                # Decode just drained: wake the scheduler policy's
                # ingest-window waiters (the retrieval batcher's ingest
                # lane) promptly.
                self._lock.notify_all()
            if req.prefix_entry is not None and self._prefix is not None:
                # Unpin the matched prefix entry: the request left its
                # slot, so LRU eviction may now recycle the store rows.
                self._prefix.release(req.prefix_entry)
                req.prefix_entry = None
            self._update_occupancy_gauges()

    def _update_occupancy_gauges(self) -> None:
        """Batch-slot occupancy + KV-cache utilization gauges (caller
        holds the lock; host-side arithmetic only)."""
        _M_SLOTS_IN_USE.set(len(self._slot_req))
        used = sum(min(p, self.max_seq_len) for p in self._slot_pos.values())
        # Utilization against the POOL (live rows / pool tokens) and
        # internal fragmentation (reserved-but-unwritten fraction of
        # live requests' pages) — the page-granular sizing signals.
        page = self.engine_config.page_size
        cap = self._kv_alloc.capacity * page
        _M_KV_UTILIZATION.set(used / cap if cap else 0.0)
        held_tokens = page * sum(
            len(p) for p in self._slot_pages.values()
        )
        self._kv_alloc.set_fragmentation(
            1.0 - used / held_tokens if held_tokens else 0.0
        )
        if self._prefix is not None and self._shared_pages_stale:
            # pool pages a store entry holds AND a live row maps: what the
            # store is sharing right now (recomputed only after a change)
            self._shared_pages_stale = False
            live = set()
            for pages in self._slot_pages.values():
                live.update(pages)
            self._prefix.note_shared_pages(live)


_REQ_IDS = itertools.count(1)
_UNSEEDED_RNG = random.SystemRandom()

_ENGINE_LOCK = threading.Lock()
_ENGINE: Optional[LLMEngine] = None


def get_engine(config: Optional[EngineConfig] = None) -> LLMEngine:
    """Process-wide engine singleton (weights live once in HBM)."""
    global _ENGINE
    with _ENGINE_LOCK:
        if _ENGINE is None:
            from generativeaiexamples_tpu.config import get_config

            _ENGINE = LLMEngine(config or get_config().engine)
        return _ENGINE


def live_queue_depth() -> Optional[int]:
    """Admission-queue depth of the process's LIVE engine, or None when
    no engine exists (remote-LLM deployments). Never builds one — both
    servers decorate their 429 sheds with this (X-GenAI-Queue-Depth,
    the routing tier's bounded-load spill signal) and a shed must stay
    cheap."""
    eng = _ENGINE
    if eng is None:
        return None
    try:
        return int(eng.queue_depth())
    except Exception:  # noqa: BLE001 - a shed header must never fail the shed
        return None


# Set once the background warmup finishes (or was never needed): pollers
# (the server's /internal/ready, chip_smoke.py) use this to keep
# multi-minute XLA compiles out of measured windows — a cold compile
# cache otherwise lands nondeterministically inside the first requests.
WARMUP_DONE = threading.Event()
WARMUP_DONE.set()


def warmup_complete() -> bool:
    """Whether no background warmup is pending (never started counts)."""
    return WARMUP_DONE.is_set()


# Set by the dispatch-loop watchdog (or a failed shutdown join) when the
# engine stops making progress with work outstanding; the servers'
# readiness probes read it so orchestrators stop routing traffic here.
ENGINE_WEDGED = threading.Event()


def engine_wedged() -> bool:
    """Whether the watchdog currently considers the engine wedged."""
    return ENGINE_WEDGED.is_set()


def start_background_warmup(engine_config: Optional[EngineConfig] = None):
    """Build the engine singleton and pre-compile its serving shapes
    on a daemon thread, where EngineConfig.warmup_prompt_lengths /
    APP_ENGINE_WARMUPPROMPTLENGTHS is non-empty (the switch; its values
    select nothing: the one warm walk covers every prompt length).

    Shared by the chain-server and the OpenAI-compatible facade: without
    warming, the first request of each shape stalls on a multi-minute
    XLA compile of the serving graph (~5 min measured for an 8B prefill
    mid-serving, BASELINE.md). Never raises — a malformed config logs
    and returns None (warmup must not kill serving).
    """
    if engine_config is None:
        from generativeaiexamples_tpu.config import get_config

        engine_config = get_config().engine
    raw = (getattr(engine_config, "warmup_prompt_lengths", "") or "").strip()
    parts = [x.strip() for x in raw.replace(";", ",").split(",") if x.strip()]
    if not all(x.isdigit() for x in parts):
        logger.warning(
            "Invalid warmup_prompt_lengths %r (want comma-separated ints); "
            "skipping warmup",
            raw,
        )
        return None
    if not parts:
        return None

    WARMUP_DONE.clear()

    def _run() -> None:
        try:
            # Plain `import jax` first: the retrieval-warmup thread may be
            # importing jax concurrently, and two threads entering via
            # different jax submodules can trip import deadlock avoidance
            # into partially initialized modules. The bare package import
            # blocks cleanly on jax's module lock.
            import jax  # noqa: F401

            t0 = time.time()
            engine = get_engine(engine_config)
            t1 = time.time()
            engine.warmup()
            logger.info(
                "Engine warmup complete "
                "(engine build %.1f s, warmup %.1f s; %s)",
                t1 - t0, time.time() - t1, engine.device_memory_line(),
            )
        except Exception:  # noqa: BLE001 - warmup must not kill serving
            # Serving continues (requests compile on demand), but loudly:
            # the traceback is what chip_smoke.py's log scan fails on.
            logger.exception("Engine warmup failed")
        finally:
            WARMUP_DONE.set()

    thread = threading.Thread(target=_run, daemon=True, name="engine-warmup")
    thread.start()
    return thread
