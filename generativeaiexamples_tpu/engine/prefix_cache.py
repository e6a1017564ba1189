"""Host-side radix index for the automatic prefix KV cache.

Every chain in this stack front-loads a large shared prefix —
``developer_rag``/``simple_rag`` prepend the same system prompt +
instruction template to every request, and ``multi_turn`` re-sends the
full conversation history each turn — yet the engine used to re-prefill
those tokens from scratch on every submit. Production serving engines
(RTP-LLM, SGLang's RadixAttention; see PAPERS.md) take their largest
TTFT wins from automatic prefix reuse; this module is the host-side half
of that optimization for the TPU engine:

- a **radix/trie index** over chunk-aligned token spans (one node per
  ``prefill_chunk``-sized span, keyed by the span's exact token tuple —
  content-addressed, no hash collisions);
- **entries** mapping a trie depth to the refcounted pool pages that
  hold the prefix's KV rows (the engine sets ``entry.pages``; this
  module never touches jax), each holding one of ``slots`` entry-count
  tickets (``store_slot``) that bound the index;
- for a model family whose slot also holds a FIXED STATE (a recurrent
  state beside the pages: models/registry.py ``state_row_keys``) the
  index is built ``stateful``: ticket ``k`` is then ALSO row
  ``num_slots + k`` of the family's per-slot arrays, where the engine
  copies the slot's state as it stood at the entry's depth (between two
  chunks of the admission that inserts it) and from where a hit copies
  it back before the first uncached chunk. Such an entry serves ITS
  depth only: a match returns the deepest depth on the prompt's path
  that HAS an entry whose copy is enqueued (``mark_ready``), never a
  shallower prefix of a deeper entry (its pages would be there, the
  state at that depth is not). An insert takes over the ticket of the
  entry its request entered through (``via``), so a conversation keeps
  one row however many turns it has; everything else is LRU;
- **refcounts** pinning a matched entry across the match → page-map
  window, so LRU eviction cannot drop an entry whose pages an
  admission is about to retain;
- **LRU eviction** over unpinned entries when the tickets run out;
- optional **session hints** (``SamplingParams.prefix_hint``): a
  hint names the chain/session a request belongs to, giving O(1)
  recency bumps at submit time so an active session's prefix survives
  eviction pressure between turns. Matching itself is content-based —
  hints are an optimization, never a correctness input.

Chunk alignment is load-bearing: cached lengths are multiples of
``prefill_chunk``, so a warm request re-enters the chunked-prefill
ladder exactly at a chunk boundary and the engine's fixed-shape extend
dispatches (and their compiled executable set) stay untouched. A match
is additionally capped at ``len(prompt) - 1`` tokens: the engine always
runs at least one real prefill chunk so it has logits to sample the
first token from.

Thread-safety: one internal lock. ``match``/``insert`` run on the
engine dispatch thread, ``touch`` on server submit threads, ``release``
on dispatch (slot release) — all short critical sections over pure
Python state.
"""
from __future__ import annotations

import threading
from typing import Dict, List, Optional, Sequence, Tuple

from generativeaiexamples_tpu.utils import metrics as metrics_mod

_REG = metrics_mod.get_registry()
_M_HITS = _REG.counter(
    "genai_engine_prefix_cache_hits_total",
    "Chunked-prefill admissions that matched a cached prefix.",
)
_M_MISSES = _REG.counter(
    "genai_engine_prefix_cache_misses_total",
    "Chunked-prefill admissions that found no cached prefix.",
)
_M_EVICTIONS = _REG.counter(
    "genai_engine_prefix_cache_evictions_total",
    "Prefix entries evicted (LRU over unpinned entries) to free a store slot.",
)
_M_TOKENS_REUSED = _REG.counter(
    "genai_engine_prefix_cache_tokens_reused_total",
    "Prompt tokens served from cached KV rows instead of prefill compute.",
)
_M_ROWS_UTIL = _REG.gauge(
    "genai_engine_prefix_cache_rows_utilization_ratio",
    "Fraction of reserved prefix-cache rows holding live cached prefixes.",
)
# Slot occupancy is the ACTIONABLE sizing signal: every entry consumes a
# whole store slot regardless of its prefix length, so the rows ratio
# can sit near zero while every insert is forced to evict.
_M_SLOTS_IN_USE = _REG.gauge(
    "genai_engine_prefix_cache_slots_in_use",
    "Reserved store slots currently holding a cached prefix entry.",
)
_M_STATE_ROWS = _REG.gauge(
    "genai_engine_prefix_state_rows_in_use",
    "Store rows of a fixed-state family's per-slot arrays that hold the "
    "state of a cached prefix (a stateful index: one row an entry).",
)
_M_SHARED_PAGES = _REG.gauge(
    "genai_engine_prefix_shared_pages_in_use",
    "Pool pages held by a prefix-store entry AND mapped by at least one "
    "live row: what the store is sharing right now.",
)
_M_SLOTS_CAPACITY = _REG.gauge(
    "genai_engine_prefix_cache_slots_capacity",
    "Configured prefix-cache store slot count (prefix_cache_slots).",
)


def metrics_snapshot() -> Dict[str, float]:
    """Legacy flat-dict keys for the engine's ``metrics`` property."""
    return {
        "prefix_cache_hits": _M_HITS.value,
        "prefix_cache_misses": _M_MISSES.value,
        "prefix_cache_evictions": _M_EVICTIONS.value,
        "prefix_cache_tokens_reused": _M_TOKENS_REUSED.value,
    }


def require_paged_state(model: str, cfg, state_row_keys: Sequence[str] = ()) -> None:
    """A prefix hit maps cached PAGES into a new request's table and
    skips the cached chunks' prefill. A model with fixed per-slot state
    (a recurrent state, a window ring: models/registry.py) would start
    its suffix from a state nobody saved, UNLESS its family names the
    leaves that hold one row a slot (``state_row_keys``): an entry then
    carries a copy of those rows beside its pages. A family that names
    none (a window ring's rows are no state at one depth) is refused at
    engine build."""
    if state_row_keys:
        return
    if cfg.prefix_cache_enable != "off" and cfg.prefix_cache_slots > 0:
        raise ValueError(
            f"{model} keeps a fixed per-slot state beside the page pool, "
            "which prefix-cache reuse can carry only for a family that "
            "registers state_row_keys (models/registry.py: the leaves that "
            "hold one row a slot, copied to a store row beside an entry's "
            "pages); this family registers none; set "
            "prefix_cache_enable='off'"
        )


class _Node:
    __slots__ = ("children", "entry", "parent")

    def __init__(self, parent: Optional["_Node"] = None) -> None:
        self.children: Dict[Tuple[int, ...], "_Node"] = {}
        self.entry: Optional["PrefixEntry"] = None
        self.parent = parent


class PrefixEntry:
    """A cached prefix: ``length`` chunk-aligned tokens whose KV rows
    live in the refcounted pool pages listed in ``pages`` (the engine
    sets it right after ``insert_entry`` returns; the allocator
    refcount is what keeps the rows alive). ``store_slot`` is the
    entry's ticket out of ``prefix_cache_slots``."""

    __slots__ = ("store_slot", "length", "refs", "last_use", "node", "pages", "ready")

    def __init__(self, store_slot: int, length: int, node: _Node, ready: bool = True) -> None:
        self.store_slot = store_slot
        self.length = length
        self.refs = 0
        self.last_use = 0
        self.node = node
        self.pages = None  # List[int] of pool pages
        # a stateful index: False until the copy of the slot's state into
        # the entry's store row is enqueued (``mark_ready``)
        self.ready = ready


class PrefixCache:
    """Radix index over chunk-aligned token prefixes → entries."""

    def __init__(self, chunk: int, slots: int, max_len: int,
                 on_drop=None, stateful: bool = False) -> None:
        if chunk <= 0 or slots <= 0 or max_len <= 0:
            raise ValueError(
                f"PrefixCache needs positive chunk/slots/max_len, got "
                f"chunk={chunk} slots={slots} max_len={max_len}"
            )
        self.chunk = chunk
        self.capacity = slots
        self.max_len = max_len
        # an entry holds a fixed-state row beside its pages and serves
        # its own depth only (module docstring)
        self.stateful = stateful
        # Called (under the cache lock) with every entry that leaves the
        # index — LRU eviction, slot invalidation, subsumed-ancestor
        # consolidation. The paged engine hooks this to release the
        # entry's refcounted pool pages; the hook must not call back
        # into this cache.
        self._on_drop = on_drop
        self._root = _Node()  # guarded by self._lock
        self._free: List[int] = list(range(slots))  # guarded by self._lock
        self._entries: List[PrefixEntry] = []  # guarded by self._lock
        self._hints: Dict[str, PrefixEntry] = {}  # guarded by self._lock
        self._tick = 0  # guarded by self._lock
        self._lock = threading.Lock()
        _M_ROWS_UTIL.set(0.0)
        _M_SLOTS_IN_USE.set(0)
        _M_SLOTS_CAPACITY.set(slots)
        _M_STATE_ROWS.set(0)

    # -- internals (caller holds self._lock) ---------------------------- #
    def _cap(self, n: int) -> int:
        """Largest chunk-aligned cacheable length for an n-token prompt:
        a multiple of ``chunk``, <= n-1 (one chunk of real prefill always
        remains to produce first-token logits), <= store row capacity."""
        c = min(n - 1, self.max_len)
        return (c // self.chunk) * self.chunk if c >= self.chunk else 0

    def _spans(self, ids: Sequence[int], upto: int):
        for i in range(0, upto, self.chunk):
            yield tuple(ids[i:i + self.chunk])

    def _walk(self, ids: Sequence[int], cap: int) -> Tuple[_Node, int]:
        """Deepest trie node whose root-path spans equal ``ids``' chunks
        (up to ``cap`` tokens), plus its depth in tokens. Caller holds
        self._lock."""
        node, depth = self._root, 0
        for key in self._spans(ids, cap):
            child = node.children.get(key)
            if child is None:
                break
            node, depth = child, depth + self.chunk
        return node, depth

    @staticmethod
    def _subtree_entry(node: _Node) -> Optional[PrefixEntry]:
        """Any entry at-or-below ``node``. A radix cache serves PARTIAL
        prefixes: if an entry's prompt shares this node's root path, its
        store rows [0:depth] are exactly the KV for that shared prefix
        (rows are causal — they depend only on preceding tokens), so any
        subtree entry can serve a match at this node's depth."""
        stack = [node]
        while stack:
            n = stack.pop()
            if n.entry is not None:
                return n.entry
            stack.extend(n.children.values())
        return None

    # Session hints are unbounded user input (one per conversation):
    # cap the map so a long-running server can't leak a dict entry per
    # conversation forever. Oldest-bound wins eviction — the entries
    # themselves are untouched (hints are advisory recency only).
    _HINT_CAP = 256

    def _bind_hint(self, hint: str, entry: PrefixEntry) -> None:
        """Bind a session hint to an entry (bounded map). Caller holds
        self._lock."""
        if hint in self._hints:
            del self._hints[hint]  # re-insert to refresh dict order
        self._hints[hint] = entry
        while len(self._hints) > self._HINT_CAP:
            self._hints.pop(next(iter(self._hints)))

    def _update_gauge(self) -> None:
        """Refresh the rows/slots gauges. Caller holds self._lock."""
        used = sum(e.length for e in self._entries)
        _M_ROWS_UTIL.set(used / (self.capacity * self.max_len))
        _M_SLOTS_IN_USE.set(self.capacity - len(self._free))
        if self.stateful:
            _M_STATE_ROWS.set(self.capacity - len(self._free))

    def _deepest_ready(self, ids: Sequence[int], cap: int) -> Optional[PrefixEntry]:
        """The deepest entry ON ``ids``' path (up to ``cap`` tokens)
        whose state copy is enqueued. Caller holds self._lock."""
        node, best = self._root, None
        for key in self._spans(ids, cap):
            node = node.children.get(key)
            if node is None:
                break
            if node.entry is not None and node.entry.ready:
                best = node.entry
        return best

    def _remove(self, entry: PrefixEntry) -> None:
        """Take ``entry`` out of the index (not its ticket: the caller
        frees or hands it on). Caller holds self._lock."""
        entry.node.entry = None
        self._entries.remove(entry)
        if self._on_drop is not None:
            self._on_drop(entry)
        for hint in [h for h, e in self._hints.items() if e is entry]:
            del self._hints[hint]

    def _evict_one(self) -> Optional[int]:
        """Free the LRU unpinned entry's store slot; None if every entry
        is pinned by a live request (refs > 0) — insertion then skips
        rather than corrupting rows under a live decode. Caller holds
        self._lock."""
        victims = [e for e in self._entries if e.refs == 0]
        if not victims:
            return None
        victim = min(victims, key=lambda e: e.last_use)
        self._remove(victim)
        self._prune(victim.node)
        _M_EVICTIONS.inc()
        return victim.store_slot

    @staticmethod
    def _prune(node: Optional[_Node]) -> None:
        """Drop now-useless trie branches (no entry anywhere below):
        partial matches resolve through subtree entries, so childless
        entry-less nodes can never serve one again."""
        while (
            node is not None
            and node.parent is not None
            and not node.children
            and node.entry is None
        ):
            parent = node.parent
            for key, child in list(parent.children.items()):
                if child is node:
                    del parent.children[key]
                    break
            node = parent

    # -- engine-facing API ---------------------------------------------- #
    def match(self, ids: Sequence[int],
              hint: Optional[str] = None) -> Optional[Tuple[PrefixEntry, int]]:
        """Deepest cached prefix of ``ids``: returns (entry, length)
        with length chunk-aligned and < len(ids); the entry is pinned
        (refs+1) until the engine calls ``release``. The length may be
        SHORTER than the entry — a radix cache serves any prefix of a
        cached prefix from the same store rows (they're causal). None —
        and a miss counted — when nothing is cached; prompts too short
        to ever reuse a chunk (len <= chunk) return None without
        counting."""
        with self._lock:
            cap = self._cap(len(ids))
            if cap <= 0:
                return None
            self._tick += 1
            if self.stateful:
                entry, depth = self._deepest_ready(ids, cap), cap
            else:
                node, depth = self._walk(ids, cap)
                entry = self._subtree_entry(node) if depth > 0 else None
            if entry is None:
                _M_MISSES.inc()
                return None
            length = min(depth, entry.length)
            entry.refs += 1
            entry.last_use = self._tick
            if hint:
                self._bind_hint(hint, entry)
            _M_HITS.inc()
            _M_TOKENS_REUSED.inc(length)
            return entry, length

    def release(self, entry: PrefixEntry) -> None:
        """Unpin a matched entry (the request left its decode slot)."""
        with self._lock:
            entry.refs = max(0, entry.refs - 1)

    def evict_lru(self) -> bool:
        """Drop the LRU unpinned entry and free its slot — page-pool
        backpressure: the engine calls this when an admission
        cannot fund its page reservation, reclaiming pages held only by
        cold cached prefixes (the drop hook releases them). False when
        every entry is pinned (or the cache is empty)."""
        with self._lock:
            slot = self._evict_one()
            if slot is None:
                return False
            self._free.append(slot)
            self._update_gauge()
            return True

    def touch(self, hint: str) -> None:
        """Session keep-alive: bump the hinted entry's recency so an
        active session's prefix survives LRU pressure between turns."""
        with self._lock:
            entry = self._hints.get(hint)
            if entry is not None:
                self._tick += 1
                entry.last_use = self._tick

    def insert(self, ids: Sequence[int],
               hint: Optional[str] = None) -> Optional[Tuple[int, int]]:
        """Register ``ids``' chunk-aligned prefix after its prefill
        completed. Returns the new entry's (store_slot, length), or
        None when the prefix is already cached at full depth,
        uncacheable, or every ticket is pinned."""
        entry = self.insert_entry(ids, hint=hint)
        if entry is None:
            return None
        return entry.store_slot, entry.length

    def cacheable_len(self, n: int) -> int:
        """The depth ``insert_entry`` names for an ``n``-token prompt (0: none)."""
        return self._cap(n)

    def mark_ready(self, entry: PrefixEntry) -> None:
        """A stateful entry's state copy is enqueued: it may be matched."""
        with self._lock:
            entry.ready = True

    def discard(self, entry: PrefixEntry) -> None:
        """Drop an entry its admission could not complete (no eviction
        counted); a no-op where it already left the index."""
        with self._lock:
            if entry.node.entry is entry:
                self._remove(entry)
                self._prune(entry.node)
                self._free.append(entry.store_slot)
                self._update_gauge()

    def insert_entry(self, ids: Sequence[int], hint: Optional[str] = None,
                     via: Optional[PrefixEntry] = None) -> Optional[PrefixEntry]:
        """``insert`` returning the entry itself — the paged engine
        needs it to attach the donated page list (``entry.pages``)
        instead of running a slot->store copy program. A stateful index
        hands back an entry that is not ``ready``: the engine enqueues
        the state copy into its row, then calls ``mark_ready``; ``via``
        is the entry the request entered through, whose ticket (and
        row) the new entry takes over where it lies on the same path
        and nobody else holds it."""
        with self._lock:
            cap = self._cap(len(ids))
            if cap <= 0:
                return None
            have, depth = self._walk(ids, cap)
            if self.stateful:
                if depth >= cap and have.entry is not None:
                    return None  # this depth has its state already
                return self._insert_at(ids, cap, hint, self._on_path(via, ids, cap))
            sub = self._subtree_entry(have)
            if depth >= cap and sub is not None:
                return None  # every cacheable row already served
            # Branch-point heuristic: diverging INSIDE a cached branch
            # (an entry continues deeper than our walk, and no entry
            # ends exactly where we diverged) with MOST of our cacheable
            # prefix already served means this prompt shares the
            # preamble but carries a one-off sibling tail (a RAG
            # question, a per-request context) — caching it would pay a
            # whole-prompt copy and burn a store slot per request for
            # rows partial matching already serves. Pure EXTENSIONS (an
            # entry ends exactly at our matched depth — e.g. a chat
            # history that grew by a turn) still deepen, with ancestor
            # consolidation keeping that to one slot per conversation;
            # and a mostly-new prompt (shared depth < half its cap —
            # e.g. a different chain whose template merely opens with
            # the same chunk) still caches its own prefix.
            if (
                sub is not None
                and have.entry is None
                and 0 < depth < sub.length
                and depth * 2 >= cap
            ):
                return None
            return self._insert_at(ids, cap, hint, None)

    def _on_path(self, via: Optional[PrefixEntry], ids: Sequence[int], cap: int) -> List[PrefixEntry]:
        """``[via]`` where it is still indexed, unpinned and an ancestor
        of ``ids``' depth ``cap``; else nothing. Caller holds self._lock."""
        if via is None or via.refs or via.node.entry is not via or via.length >= cap:
            return []
        node, _ = self._walk(ids, via.length)
        return [via] if node is via.node else []

    def _insert_at(self, ids: Sequence[int], cap: int, hint: Optional[str],
                   takeover: Optional[List[PrefixEntry]]) -> Optional[PrefixEntry]:
        """Create the entry at depth ``cap`` of ``ids``' path. ``takeover``
        None: every unpinned ancestor entry on the path is consolidated
        into it; a list: exactly those. Caller holds self._lock."""
        node = self._root
        subsumed: List[PrefixEntry] = []
        for key in self._spans(ids, cap):
            child = node.children.get(key)
            if child is None:
                child = _Node(parent=node)
                node.children[key] = child
            node = child
            if takeover is None and child.entry is not None and child.entry.refs == 0:
                subsumed.append(child.entry)
        if takeover is not None:
            subsumed = takeover
        # Consolidate unpinned ANCESTOR entries along this path: the
        # new deeper entry serves every prefix they served (partial
        # matching), so their slots are pure duplication — reclaim
        # them instead of LRU-evicting other chains' preambles (a
        # growing multi-turn conversation would otherwise fill the
        # store with nested copies of itself). Not counted as
        # evictions: no cached content becomes unservable.
        for dup in subsumed:
            self._remove(dup)
            self._free.append(dup.store_slot)
        if self._free:
            slot = self._free.pop()
        else:
            slot = self._evict_one()
            if slot is None:
                self._update_gauge()
                return None
        self._tick += 1
        entry = PrefixEntry(slot, cap, node, ready=not self.stateful)
        entry.last_use = self._tick
        node.entry = entry
        self._entries.append(entry)
        if hint:
            self._bind_hint(hint, entry)
        self._update_gauge()
        return entry

    def note_shared_pages(self, live_pages) -> int:
        """Set the gauge of pool pages that an entry holds and a live row
        maps (``live_pages``: the set of pages in live rows' tables)."""
        with self._lock:
            held = set()
            for e in self._entries:
                held.update(e.pages or ())
        shared = len(held & live_pages)
        _M_SHARED_PAGES.set(shared)
        return shared

    # -- introspection --------------------------------------------------- #
    def stats(self) -> Dict[str, float]:
        with self._lock:
            return {
                "entries": len(self._entries),
                "free_slots": len(self._free),
                "cached_rows": sum(e.length for e in self._entries),
                "capacity_rows": self.capacity * self.max_len,
            }
