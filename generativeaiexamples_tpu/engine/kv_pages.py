"""Host-side page allocator for the engine's KV cache (docs/paged_kv.md).

A dense ``max_seq_len`` strip per decode slot would make a 48-token chat
answer and an 8k-token RAG prompt cost the same HBM, and a prefix-cache
hit would have to COPY rows into the slot strip. The cache (the TPU
analogue of vLLM's PagedAttention; PAPERS.md "Ragged Paged Attention")
is instead broken into fixed-size pages owned by this allocator:

- a **free list** over a device-resident page pool (page 0 is reserved
  as the scratch page — masked/dead writes land there, so stale page
  tables can never scribble on a live request's rows);
- **per-request page tables** built at admission: the engine reserves
  every page a request can touch up front (prompt + generation budget +
  dispatch slack), so decode/spec dispatches never allocate and the
  pool can never over-commit mid-stream;
- **refcounted pages** shared zero-copy between a prefix-cache entry
  and every request whose prompt starts with that prefix: a radix hit
  maps the shared pages into the new request's page table (refcount
  bump) instead of dispatching gather/update copy programs, and the
  post-prefill insert donates the request's own prompt pages the same
  way;
- **OOM backpressure**: ``alloc`` returns None when the free list is
  short — admission requeues the request (after LRU-evicting unpinned
  prefix entries to reclaim their pages) instead of corrupting live
  rows.

Everything here is pure host state behind one lock — no jax imports, so
the metric linters and pure-host tier-1 tests load it freely.
"""
from __future__ import annotations

import dataclasses
import threading
from typing import Dict, List, Optional, Sequence

from generativeaiexamples_tpu.utils import metrics as metrics_mod

_REG = metrics_mod.get_registry()
_M_ALLOCS = _REG.counter(
    "genai_engine_kv_page_allocs_total",
    "KV-cache pages handed to requests by the page allocator.",
)
_M_FREES = _REG.counter(
    "genai_engine_kv_page_frees_total",
    "KV-cache pages whose refcount dropped to zero and returned to the "
    "free list.",
)
_M_ALLOC_FAILURES = _REG.counter(
    "genai_engine_kv_page_alloc_failures_total",
    "Admission page reservations refused because the free list was "
    "short (the request is requeued — OOM backpressure, not an error).",
)
_M_PREFIX_MAPPED = _REG.counter(
    "genai_engine_kv_prefix_pages_mapped_total",
    "Prefix-cache pages mapped zero-copy into a request's page table "
    "(refcount bump instead of a store->slot copy dispatch).",
)
_M_POOL_IN_USE = _REG.gauge(
    "genai_engine_kv_page_pool_in_use",
    "Pages currently held by live requests or prefix-cache entries.",
)
_M_POOL_CAPACITY = _REG.gauge(
    "genai_engine_kv_page_pool_capacity",
    "Allocatable pages in the device page pool (scratch page excluded).",
)
_M_POOL_UTIL = _REG.gauge(
    "genai_engine_kv_page_utilization_ratio",
    "Fraction of the page pool currently allocated.",
)
_M_FRAGMENTATION = _REG.gauge(
    "genai_engine_kv_page_fragmentation_ratio",
    "Internal fragmentation: fraction of live requests' allocated page "
    "tokens not (yet) holding sequence state — bounded below one page "
    "plus the reserved generation budget per request.",
)
_M_FIXED_STATE_BYTES = _REG.gauge(
    "genai_engine_fixed_state_bytes",
    "Device bytes of per-slot state that is not paged (recurrent states, "
    "window rings) and sits beside the page pool; 0 for a model whose "
    "every layer is paged.",
)
_M_REQUEST_PAGES = _REG.histogram(
    "genai_engine_kv_request_pages",
    "Pages a request held over its lifetime, observed at release.",
    buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256, 512),
)


def metrics_snapshot() -> Dict[str, float]:
    """Legacy flat-dict keys for the engine's ``metrics`` property."""
    return {
        "kv_page_allocs": _M_ALLOCS.value,
        "kv_page_frees": _M_FREES.value,
        "kv_page_alloc_failures": _M_ALLOC_FAILURES.value,
        "kv_prefix_pages_mapped": _M_PREFIX_MAPPED.value,
        "kv_pages_in_use": _M_POOL_IN_USE.value,
        "kv_page_utilization": _M_POOL_UTIL.value,
    }


def record_prefix_mapped(pages: int) -> None:
    """Count pages mapped zero-copy from a prefix-cache hit."""
    _M_PREFIX_MAPPED.inc(pages)


def record_alloc_failure() -> None:
    """Count one real OOM-backpressure event (an admission that could
    not be funded even after evicting unpinned prefix entries and was
    requeued) — used by callers that retried with
    ``alloc(count_failure=False)``."""
    _M_ALLOC_FAILURES.inc()
    # Anomaly black box: N give-ups inside the storm window capture a
    # debug bundle (one boolean read when disabled; utils/blackbox.py).
    from generativeaiexamples_tpu.utils import blackbox

    blackbox.notify_page_backpressure()


SCRATCH_PAGE = 0


def pages_for_tokens(tokens: int, page_size: int) -> int:
    """Pages covering ``tokens`` rows (ceil)."""
    return (max(0, tokens) + page_size - 1) // page_size


def page_bytes(
    layers: int,
    page_size: int,
    kv_heads: int,
    head_dim: int,
    quantized: bool,
    dtype_bytes: int = 2,
    kv_width: float | None = None,
) -> int:
    """HBM bytes ONE pool page represents across every layer: k+v rows
    (quantized storage adds the float32 per-(token, kv-head) scales —
    ``page_size * Hkv`` of them a page per direction, whichever way the
    pool stores them: lane-dense ``[page_size * Hkv / 128, 128]`` or
    token-major ``[page_size, Hkv]``, models/llama.py
    ``kv_scale_plane_shape`` decides; the bytes are the same, only
    the chip's padding of the token-major form is not counted here).
    This is the handoff protocol's per-page transfer accounting
    (engine/scheduler/handoff.py): what a cross-replica transport would
    put on the wire, and zero actual device traffic on the same-host
    shared-pool path. ``kv_width`` overrides the per-element width for
    sub-byte storage (utils/hardware.kv_bytes_per_element — int4 packs
    two values per byte, 0.5); the default keeps the historical int8=1
    / dense=dtype_bytes arithmetic."""
    if kv_width is not None:
        width = kv_width
    else:
        width = 1 if quantized else dtype_bytes
    nbytes = int(2 * layers * page_size * kv_heads * head_dim * width)
    if quantized:
        nbytes += 2 * layers * page_size * kv_heads * 4
    return nbytes


@dataclasses.dataclass(frozen=True)
class CachePlan:
    """The two kinds of cache one engine can hold (docs/model_registry.md):
    ``paged_bytes`` grow with the sequences (the pool this allocator
    hands out page by page), ``fixed_bytes`` are a model's per-slot
    state that no sequence length changes (a recurrent state, a window
    ring) and that lives beside the pool, indexed by slot, never by
    page. A model whose every layer is paged has ``fixed_bytes == 0``."""

    pool_pages: int
    page_size: int
    slots: int
    paged_bytes_per_token: int
    fixed_bytes_per_slot: int

    @property
    def paged_bytes(self) -> int:
        return self.pool_pages * self.page_size * self.paged_bytes_per_token

    @property
    def fixed_bytes(self) -> int:
        return self.slots * self.fixed_bytes_per_slot

    @property
    def total_bytes(self) -> int:
        return self.paged_bytes + self.fixed_bytes


def cache_plan(pool_pages: int, page_size: int, slots: int,
               paged_bytes_per_token: int, fixed_bytes_per_slot: int = 0) -> CachePlan:
    """The cache plan of one engine; publishes the fixed share as a gauge."""
    plan = CachePlan(pool_pages, page_size, slots, paged_bytes_per_token, fixed_bytes_per_slot)
    _M_FIXED_STATE_BYTES.set(plan.fixed_bytes)
    return plan


def pages_needed(
    prompt_len: int,
    max_tokens: int,
    page_size: int,
    max_seq_len: int,
    slack: int,
) -> int:
    """Worst-case pages one request can touch: prompt + generation
    budget + ``slack`` dispatch-overrun tokens (in-flight decode blocks
    and spec-verify chunks keep writing for up to a block past a
    request's budget before the eager release lands), capped at the
    per-slot capacity. Reserving this at admission is what makes the
    pool accounting exact — no dispatch ever allocates."""
    return pages_for_tokens(
        min(prompt_len + max_tokens + slack, max_seq_len), page_size
    )


def pool_pages(cfg, max_seq_len: int, prefix_slots: int = 0) -> int:
    """Pool size in pages. ``kv_pool_pages`` when set; otherwise one
    full-capacity strip of pages per decode slot plus one per
    prefix-cache entry (entries hold refcounted pool pages) — plus the
    scratch page."""
    if cfg.kv_pool_pages > 0:
        return cfg.kv_pool_pages
    per_slot = pages_for_tokens(max_seq_len, page_size=cfg.page_size)
    return 1 + (cfg.max_batch_size + max(0, prefix_slots)) * per_slot


def validate_config(cfg) -> None:
    """Pure-host validation of the KV-cache knobs (engine init and
    server startup share this). A geometry that cannot page fails
    start-up here or in :func:`validate_runtime`: there is no other
    layout to serve it."""
    if cfg.kv_pool_pages < 0:
        raise ValueError(
            f"kv_pool_pages must be >= 0 (0 = auto-size), got "
            f"{cfg.kv_pool_pages}"
        )
    if getattr(cfg, "paged_kernel", "auto") not in (
        "auto", "off", "interpret"
    ):
        raise ValueError(
            f"paged_kernel must be auto|off|interpret, got "
            f"{cfg.paged_kernel!r}"
        )
    p = cfg.page_size
    if p <= 0 or (p & (p - 1)) != 0:
        raise ValueError(
            f"page_size must be a positive power of two, got {p}"
        )
    if p > 128:
        # Attention windows are bucketed in power-of-two token rungs
        # starting at 128; a page larger than the smallest rung could
        # not tile every rung.
        raise ValueError(
            f"page_size must divide the 128-token attention-window rung "
            f"(<= 128), got {p}"
        )
    if cfg.prefill_chunk % p:
        raise ValueError(
            f"prefill_chunk ({cfg.prefill_chunk}) must be a multiple of "
            f"page_size ({p}) so chunk-aligned prefix-cache entries are "
            f"page-aligned (zero-copy sharing needs whole pages)"
        )


def validate_runtime(page_size: int, max_seq_len: int, pool: int) -> None:
    """Checks that need the EFFECTIVE sequence capacity (config cap
    min'd with the model's) and the resolved pool size."""
    if max_seq_len % page_size:
        raise ValueError(
            f"effective max_seq_len ({max_seq_len}) must be a multiple "
            f"of page_size ({page_size})"
        )
    min_rung = min(128, max_seq_len)
    if min_rung % page_size:
        raise ValueError(
            f"page_size ({page_size}) must divide the smallest "
            f"attention-window rung ({min_rung})"
        )
    per_slot = pages_for_tokens(max_seq_len, page_size)
    if pool < 1 + per_slot:
        raise ValueError(
            f"kv_pool_pages ({pool}) cannot hold even one full-length "
            f"request ({per_slot} pages + 1 scratch)"
        )


class PageAllocator:
    """Refcounted free-list allocator over the device page pool.

    Thread-safe behind one lock; all methods are O(pages touched).
    Page 0 (``SCRATCH_PAGE``) is never handed out.
    """

    def __init__(self, pool: int, page_size: int) -> None:
        if pool < 2:
            raise ValueError(f"page pool needs >= 2 pages, got {pool}")
        if page_size <= 0:
            raise ValueError(f"page_size must be > 0, got {page_size}")
        self.pool = pool
        self.page_size = page_size
        self.capacity = pool - 1  # scratch page excluded
        # pop() hands out page 1 first
        self._free: List[int] = list(range(pool - 1, 0, -1))  # guarded by self._lock
        self._refs: Dict[int, int] = {}  # guarded by self._lock
        # Live-occupancy basis (bench A/B + paged_stats): every state
        # transition samples pages-in-use, so mean/peak describe the
        # occupancy the attention pass actually read over the window —
        # ONE accessor instead of each consumer recomputing its own
        # mean-live estimate.
        self._occ_sum = 0  # guarded by self._lock
        self._occ_samples = 0  # guarded by self._lock
        self._occ_peak = 0  # guarded by self._lock
        self._lock = threading.Lock()
        _M_POOL_CAPACITY.set(self.capacity)
        _M_POOL_IN_USE.set(0)
        _M_POOL_UTIL.set(0.0)
        _M_FRAGMENTATION.set(0.0)

    # -- internals (caller holds self._lock) ---------------------------- #
    def _update_gauges(self) -> None:
        """Refresh the occupancy gauges. Caller holds self._lock."""
        used = len(self._refs)
        self._occ_sum += used
        self._occ_samples += 1
        if used > self._occ_peak:
            self._occ_peak = used
        _M_POOL_IN_USE.set(used)
        _M_POOL_UTIL.set(used / self.capacity)

    # -- engine-facing API ---------------------------------------------- #
    def alloc(self, n: int, count_failure: bool = True) -> Optional[List[int]]:
        """Reserve ``n`` fresh pages (refcount 1 each); None when the
        free list is short — the caller requeues (backpressure) rather
        than partially funding a request. ``count_failure=False`` keeps
        intermediate attempts inside an evict-and-retry loop out of the
        backpressure counter (only the final give-up is a real
        requeue-worthy failure)."""
        if n <= 0:
            return []
        with self._lock:
            if len(self._free) < n:
                if count_failure:
                    _M_ALLOC_FAILURES.inc()
                return None
            pages = [self._free.pop() for _ in range(n)]
            for p in pages:
                self._refs[p] = 1
            _M_ALLOCS.inc(n)
            self._update_gauges()
            return pages

    def retain(self, pages: Sequence[int]) -> None:
        """Refcount bump for zero-copy sharing (prefix-cache map/donate).
        Every page must already be allocated."""
        if not pages:
            return
        with self._lock:
            for p in pages:
                if p not in self._refs:
                    raise ValueError(f"retain of unallocated page {p}")
                self._refs[p] += 1

    def release(self, pages: Sequence[int]) -> int:
        """Refcount drop; pages reaching zero return to the free list.
        Returns the number of pages actually freed."""
        if not pages:
            return 0
        freed = 0
        with self._lock:
            for p in pages:
                refs = self._refs.get(p)
                if refs is None:
                    raise ValueError(f"release of unallocated page {p}")
                if refs > 1:
                    self._refs[p] = refs - 1
                else:
                    del self._refs[p]
                    self._free.append(p)
                    freed += 1
            if freed:
                _M_FREES.inc(freed)
            self._update_gauges()
        return freed

    def observe_request_pages(self, n: int) -> None:
        _M_REQUEST_PAGES.observe(n)

    def set_fragmentation(self, ratio: float) -> None:
        _M_FRAGMENTATION.set(max(0.0, min(1.0, ratio)))

    # -- introspection --------------------------------------------------- #
    def free_pages(self) -> int:
        with self._lock:
            return len(self._free)

    def used_pages(self) -> int:
        with self._lock:
            return len(self._refs)

    def refcount(self, page: int) -> int:
        with self._lock:
            return self._refs.get(page, 0)

    def all_live(self, pages: Sequence[int]) -> bool:
        """Whether every page still holds a live refcount — the handoff
        import's sanity check (engine/scheduler/handoff.py): a request
        crossing the prefill→decode tier boundary keeps the refcounts
        funded at admission, so a dead page at import means the
        reservation was released out from under the transfer and the
        request must re-prefill (counted, asserted flat)."""
        with self._lock:
            return all(self._refs.get(p, 0) > 0 for p in pages)

    def occupancy(self, reset: bool = False) -> Dict[str, float]:
        """Live-page occupancy basis over the allocator's lifetime (or
        since the last ``reset=True`` read): transition-sampled mean and
        peak pages-in-use (tools read it instead of each recomputing a
        prompt-arithmetic estimate); the peak is the same number a
        mid-run pool sampler observes."""
        with self._lock:
            out = {
                "mean_live_pages": (
                    self._occ_sum / self._occ_samples
                    if self._occ_samples else 0.0
                ),
                "peak_live_pages": float(self._occ_peak),
                "occupancy_samples": float(self._occ_samples),
            }
            if reset:
                self._occ_sum = 0
                self._occ_samples = 0
                self._occ_peak = 0
            return out

    def stats(self) -> Dict[str, float]:
        with self._lock:
            used = len(self._refs)
            shared = sum(1 for r in self._refs.values() if r > 1)
            return {
                "page_size": self.page_size,
                "pages_capacity": self.capacity,
                "pages_in_use": used,
                "pages_free": len(self._free),
                "pages_shared": shared,
                "utilization": used / self.capacity,
                "mean_live_pages": (
                    self._occ_sum / self._occ_samples
                    if self._occ_samples else 0.0
                ),
                "peak_live_pages": float(self._occ_peak),
            }
