"""Dispatch timeline: one span per launch, from enqueue to the device's
completion, and what the host and jit did meanwhile.

compile_watch says what jit did and the flight recorder decomposes a
*request's* latency; this module decomposes the *engine's* wall time.
Every compiled-program launch with a span (prefill chunk, decode block,
spec verify, spec-block fallback) is recorded at its one choke point,
the ``_dispatch_lock``, into a bounded ring of **dispatch spans**: program kind, tier thread, wall clock, dispatch-lock wait, the
enqueue call's own length (``enqueue_s``: host time inside the lock,
jit's work included), batch geometry, attention path, rids.

One chip runs the engine's programs in the order they were enqueued, and
each such launch has a small output no program donates. ONE thread (the
watcher, ``start_watcher``) awaits those outputs in enqueue order and
stamps on each span the wall time at which its output was ready
(``t_done``). From that and the enqueue time come ``device_s`` (the
device's time on this launch while anything was queued behind the last
one), ``starved_s`` (the device had nothing of the engine's to run) and
``queued_s`` (how far ahead of the device the host was). Programs
without a span of their own (finish, put_rows, update_slots,
page_tables, an embed dispatch) are charged to the next span. Sums are
conserved where single stamps are late. A launch held far longer than
its program and rung ever take leaves ONE ``device_hold:<program>``
record (``_hold_record``) of what the process can know about it.

On top of the ring:

- the **bubble decomposition** of rolling-window engine-active wall:
  ``device`` (sum of ``device_s``), ``host_gap`` (sum of ``starved_s``),
  ``lock_contention`` and ``readback`` (as measured on the host), in
  ``bubble_snapshot()`` (``GET /internal/timeline``, ``/internal/slo``)
  and cumulatively in ``counters_snapshot()`` (the loadgen scraper);
- ``genai_engine_dispatch_device_seconds{program}``,
  ``genai_engine_device_starved_seconds_total``,
  ``genai_engine_lock_wait_seconds`` and
  ``genai_engine_dispatch_gap_seconds`` (each launch's ``starved_s``);
- ``GET /internal/timeline`` (server/observability.py) serving the ring
  incrementally (``?since=<cursor>``) and as Chrome-trace JSON
  (``?format=perfetto``): one track per tier thread plus a device
  track from the completion stamps, flight-recorder events overlaid;
- recent span windows embedded in black-box bundles (utils/blackbox.py);
- **what a token waited behind** (PR 54): with every stamp the watcher
  publishes the device's seconds by kind as ONE immutable tuple
  (``DeviceClock``, ``device_clock()``); the engine's reader takes, at
  each stream hand-off, the clock of the launch it reads back minus the
  clock the request kept at its previous hand-off (``HandoffBlock``):
  the gap's parts land on the decode span (``GAP_FIELDS``), in
  ``genai_stream_handoff_gap_seconds`` and
  ``genai_stream_handoff_gap_part_seconds_total{part}``, and a gap of
  seconds leaves ONE ``stream_gap`` record beside the hold's.

Ring semantics mirror utils/flight_recorder.py: a monotonic ``seq``
cursor, whole-window eviction (``WINDOW_SPANS`` spans drop together), a
``reset()`` test hook, the ``configure``/``validate_config``/
``configure_from_config`` trio, and the ``GENAI_DISPATCH_TIMELINE=off``
process kill switch, which turns the stamp off with the spans — the
engine resolves it ONCE at init, so 'off' restores the exact prior
dispatch path.
"""
from __future__ import annotations

import gc
import os
import queue
import statistics
import threading
import time
from collections import deque
from typing import Any, Deque, Dict, List, NamedTuple, Optional, Sequence, Tuple

from generativeaiexamples_tpu.utils import metrics as metrics_mod
from generativeaiexamples_tpu.utils.logging import get_logger

logger = get_logger(__name__)

__all__ = [
    "enabled",
    "configure",
    "validate_config",
    "configure_from_config",
    "record_span",
    "record_stall",
    "record_readback",
    "record_pipeline_flush",
    "record_rollback",
    "record_compile",
    "note_jit",
    "start_watcher",
    "stop_watcher",
    "stamp_pending",
    "DeviceClock",
    "device_clock",
    "clock_at",
    "HandoffBlock",
    "cursor",
    "spans_since",
    "recent_spans",
    "bubble_snapshot",
    "counters_snapshot",
    "perfetto_trace",
    "reset",
    "WINDOW_SPANS",
    "MODES",
]

# --------------------------------------------------------------------------- #
# Metrics (registered at import — tools/genai_lint REGISTRY_MODULES)

_REG = metrics_mod.get_registry()
_M_SPANS = _REG.counter(
    "genai_engine_timeline_spans_total",
    "Dispatch-timeline spans recorded, by span kind (dispatch program "
    "kinds plus stall/readback/compile categories).",
    ("kind",),
)
_M_EVICTED = _REG.counter(
    "genai_engine_timeline_evicted_total",
    "Dispatch-timeline spans evicted from the ring (always a whole "
    "span window at a time, oldest first).",
)
_M_LOCK_WAIT = _REG.histogram(
    "genai_engine_lock_wait_seconds",
    "Time a tier thread waited to acquire the engine dispatch lock "
    "before a compiled-program launch, by program kind — the "
    "cross-tier contention half of the bubble decomposition.",
    ("kind",),
    buckets=metrics_mod.FAST_SECONDS_BUCKETS,
)
_M_GAP = _REG.histogram(
    "genai_engine_dispatch_gap_seconds",
    "Per launch, how long the device had nothing of the engine's to run "
    "before it was enqueued (the span's starved_s: the last launch was "
    "done and the host had not enqueued the next).",
    buckets=metrics_mod.FAST_SECONDS_BUCKETS,
)
_M_DEVICE = _REG.histogram(
    "genai_engine_dispatch_device_seconds",
    "The device's time on one launch, from the later of its enqueue and "
    "the previous launch's completion to its own completion (the "
    "span's device_s; unspanned programs enqueued before it included), "
    "by program kind.",
    ("program",),
    buckets=metrics_mod.FAST_SECONDS_BUCKETS,
)
_M_STARVED = _REG.counter(
    "genai_engine_device_starved_seconds_total",
    "Seconds the device had nothing of the engine's to run between two "
    "launches (sum of the spans' starved_s).",
)

_M_HANDOFF_GAP = _REG.histogram(
    "genai_stream_handoff_gap_seconds",
    "Per stream hand-off (one put of one request's tokens of one readback), "
    "the time since that request's previous hand-off, on the reader "
    "thread's clock: the distribution whose ~96th (decode_block 8) or "
    "~99th (decode_block 2) percentile a client's 99.5th frame gap reads.",
    buckets=(0.005, 0.01, 0.015, 0.025, 0.04, 0.06, 0.1, 0.15, 0.25, 0.4,
             0.6, 1.0, 2.0, float("inf")),
)
_M_HANDOFF_GAP_PART = _REG.counter(
    "genai_stream_handoff_gap_part_seconds_total",
    "The hand-off gaps' seconds by what filled them: the device's time on "
    "decode blocks, on extend (prefill) chunks and on everything else, the "
    "device starved of the engine's work, and the host's rest (the gap "
    "minus those four). Summed over every hand-off; over the sum of "
    "genai_stream_handoff_gap_seconds it is what the mean token waits "
    "behind.",
    ("part",),
)

# --------------------------------------------------------------------------- #
# Module configuration (defaults keep the recorder ON — bare-engine and
# bench paths need no config object). GENAI_DISPATCH_TIMELINE=off is
# the process kill switch for entrypoints that never load an AppConfig;
# the engine reads enabled() ONCE at init, so 'off' leaves the dispatch
# sites byte-for-byte on the prior path.

_ENABLED = os.environ.get("GENAI_DISPATCH_TIMELINE", "on").lower() not in (
    "0", "off", "false", "no"
)

# Eviction granularity: the ring drops this many spans at once, so a
# cursor-tailing reader (or the bubble analyzer) never observes a span
# window missing interior spans — whole-window eviction, the same rule
# the flight recorder applies to whole timelines.
WINDOW_SPANS = 64
_DEFAULT_CAPACITY = 4096
_CAPACITY = _DEFAULT_CAPACITY

# Bubble analyzer rolling window (seconds of wall clock).
_BUBBLE_WINDOW_S = 60.0

# Per-span rid cap: a 96-row wave's ids matter less than its shape.
_RID_CAP = 16

# A long hold (constants, not configuration): a launch whose device_s
# passes BOTH is recorded once as device_hold:<program>.
HOLD_MIN_S = 1.0
HOLD_TIMES_MEDIAN = 8.0
_HOLD_HISTORY = 33  # device_s kept per (program, rung) for the median
_HOLD_MIN_SAMPLES = 3
HEARTBEAT_S = 0.02

_LOCK = threading.Lock()
_SPANS: Deque["Span"] = deque()  # guarded by _LOCK
_SEQ = 0  # guarded by _LOCK; process-lifetime monotonic, reset() rewinds
# Cumulative component seconds (guarded by _LOCK) — the loadgen
# telemetry scraper reads these as run-window deltas via the engine's
# legacy flat `metrics` dict.
_CUM = {"spans": 0.0, "device": 0.0, "lock": 0.0, "gap": 0.0, "readback": 0.0}

# Per-MODE bubble split (guarded by _LOCK): the same four component
# seconds plus a dispatch count, attributed to the serving mode that
# produced the span — so "spec pays its sync on the dispatch thread"
# is a number, not a code comment. A span's mode is classified from
# its kind (spec verifies, their fallback blocks, and the async
# pipeline's flush/rollback spans are 'spec'; plain decode blocks are
# 'decode'; prefill chunks and handoff stalls are 'prefill').
MODES = ("decode", "spec", "prefill", "other")
_CUM_MODE: Dict[str, Dict[str, float]] = {
    m: {"device": 0.0, "lock": 0.0, "gap": 0.0, "readback": 0.0,
        "dispatches": 0.0}
    for m in MODES
}


def _mode_of(kind: str) -> str:
    base = kind.split(":", 1)[1] if kind.startswith("readback:") else kind
    if base.startswith("spec") or base in ("pipeline_flush", "rollback"):
        return "spec"
    if base.startswith("decode"):
        return "decode"
    if base.startswith("prefill") or base.startswith("handoff"):
        return "prefill"
    return "other"


class DeviceClock(NamedTuple):
    """The device's seconds so far by what it ran: monotone sums over
    the stamped launches, as ONE immutable value that ``_stamp``
    publishes in the critical section in which it adds a launch to
    ``_CUM_MODE``, so another thread reads a consistent set as one
    attribute load (``device_clock``). ``extend_s`` is mode 'prefill',
    ``other_s`` the modes 'spec' and 'other' (verifies, prefix-state
    copies, whatever a span charges); ``launches`` counts the stamps and
    ``seq`` is the last stamped span's."""

    decode_s: float = 0.0
    extend_s: float = 0.0
    other_s: float = 0.0
    starved_s: float = 0.0
    launches: int = 0
    seq: int = 0


_CLOCK = DeviceClock()  # written under _LOCK (by _stamp and reset), read without

# What a hand-off's gap was made of, as fields of the decode span that
# handed the tokens over (HandoffBlock.close; docs/observability.md).
GAP_FIELDS = (
    "handoff_rows", "gap_s", "gap_decode_s", "gap_extend_s", "gap_other_s",
    "gap_starved_s", "gap_host_s", "gap_launches",
)
_GAP_PARTS = ("decode", "extend", "other", "starved", "host")
# resolved once: every part's series is on /metrics from the start
_M_GAP_PARTS = tuple(_M_HANDOFF_GAP_PART.labels(part=p) for p in _GAP_PARTS)


class Span:
    """One recorded launch/stall/readback. Appends are deque.append
    under the module lock; after that only the watcher writes, once, the
    completion fields of a dispatch span (and the engine ``tokens`` of a
    verify whose count lands with its readback)."""

    __slots__ = (
        "seq", "kind", "category", "thread", "t_wall", "lock_wait_s",
        "run_s", "rows", "tokens", "steps", "path", "rids", "counters",
        "t_done", "device_s", "starved_s", "queued_s", "jit_s", "jit_what",
        "clock",
    )

    def __init__(self, kind: str, category: str, thread: str,
                 t_wall: float, lock_wait_s: float, run_s: float,
                 rows: int = 0, tokens: int = 0, steps: int = 1,
                 path: Optional[str] = None, rids: Tuple[int, ...] = (),
                 counters: Optional[Dict[str, Any]] = None):
        self.seq = 0  # assigned under _LOCK at record time
        self.kind = kind
        self.category = category  # dispatch | stall | readback | compile
        self.thread = thread
        self.t_wall = t_wall
        self.lock_wait_s = lock_wait_s
        self.run_s = run_s
        self.rows = rows
        self.tokens = tokens
        self.steps = steps
        self.path = path
        self.rids = rids
        # kind-specific counts, shown as top-level fields of the view
        # (decode: kv_pages_walked / kv_pages_grid; a hold: its record)
        self.counters = counters
        self.t_done: Optional[float] = None  # the watcher's stamp
        self.clock: Optional[DeviceClock] = None  # the device clock once it holds this launch
        self.device_s = self.starved_s = self.queued_s = 0.0
        self.jit_s = 0.0
        self.jit_what: Optional[str] = None

    @property
    def t_enq(self) -> float:
        """Wall time at which the enqueue call returned."""
        return self.t_wall + self.lock_wait_s + self.run_s

    def view(self) -> Dict[str, Any]:
        out: Dict[str, Any] = {
            "seq": self.seq,
            "kind": self.kind,
            "category": self.category,
            "thread": self.thread,
            "t_wall": round(self.t_wall, 6),
            "lock_wait_s": round(self.lock_wait_s, 6),
            "rows": self.rows,
            "tokens": self.tokens,
            "steps": self.steps,
        }
        if self.category == "dispatch":
            out["t_enq"] = round(self.t_enq, 6)
            out["enqueue_s"] = round(self.run_s, 6)
            if self.t_done is not None:
                out["t_done"] = round(self.t_done, 6)
                out["device_s"] = round(self.device_s, 6)
                out["starved_s"] = round(self.starved_s, 6)
                out["queued_s"] = round(self.queued_s, 6)
            if self.jit_what is not None:
                out["jit_s"] = round(self.jit_s, 6)
                out["jit_what"] = self.jit_what
        else:
            out["duration_s"] = round(self.run_s, 6)
        if self.path is not None:
            out["path"] = self.path
        if self.rids:
            out["rids"] = list(self.rids)
        if self.counters:
            out.update(self.counters)
        return out


# --------------------------------------------------------------------------- #
# Configuration


def enabled() -> bool:
    return _ENABLED


def configure(
    enable: Optional[bool] = None,
    capacity: Optional[int] = None,
) -> None:
    """Apply config-derived knobs (the servers call
    :func:`configure_from_config` at startup; tests call this
    directly). Capacity rounds up to a whole span window so eviction
    granularity never splits one; resizing preserves the newest spans
    in whole windows."""
    global _ENABLED, _CAPACITY
    with _LOCK:
        if enable is not None:
            _ENABLED = bool(enable)
        if capacity is not None:
            cap = max(WINDOW_SPANS, int(capacity))
            cap = ((cap + WINDOW_SPANS - 1) // WINDOW_SPANS) * WINDOW_SPANS
            _CAPACITY = cap
            while len(_SPANS) > _CAPACITY:
                _evict_window_locked()


def validate_config(cfg) -> None:
    """Validate the ``observability`` dispatch-timeline knobs (pure
    host; phrasing matches the other section checks)."""
    o = cfg.observability if hasattr(cfg, "observability") else cfg
    if o.dispatch_timeline_enable not in ("on", "off"):
        raise ValueError(
            f"observability.dispatch_timeline_enable must be on|off, got "
            f"{o.dispatch_timeline_enable!r}"
        )
    if o.dispatch_timeline_capacity < WINDOW_SPANS:
        raise ValueError(
            f"observability.dispatch_timeline_capacity must be >= "
            f"{WINDOW_SPANS} (one whole span window), got "
            f"{o.dispatch_timeline_capacity}"
        )


def configure_from_config(cfg) -> None:
    """Wire the ``observability`` config section into the module knobs
    (called by the servers at startup). The env kill switch wins: a
    process started with GENAI_DISPATCH_TIMELINE=off stays off even
    when the config says 'on' — same precedence as the blackbox."""
    o = cfg.observability if hasattr(cfg, "observability") else cfg
    env_off = os.environ.get("GENAI_DISPATCH_TIMELINE", "on").lower() in (
        "0", "off", "false", "no"
    )
    configure(
        enable=(o.dispatch_timeline_enable != "off") and not env_off,
        capacity=o.dispatch_timeline_capacity,
    )


# --------------------------------------------------------------------------- #
# Recording


def _evict_window_locked() -> None:
    """Drop one whole span window from the ring head. Caller holds
    _LOCK."""
    dropped = 0
    for _ in range(min(WINDOW_SPANS, len(_SPANS))):
        _SPANS.popleft()
        dropped += 1
    if dropped:
        _M_EVICTED.inc(dropped)


def _append(span: Span) -> None:
    global _SEQ
    with _LOCK:
        _SEQ += 1
        span.seq = _SEQ
        if len(_SPANS) >= _CAPACITY:
            _evict_window_locked()
        _SPANS.append(span)
        _CUM["spans"] += 1
        mode = _CUM_MODE[_mode_of(span.kind)]
        if span.category == "dispatch":
            _CUM["lock"] += span.lock_wait_s
            mode["lock"] += span.lock_wait_s
            mode["dispatches"] += 1
        elif span.category == "readback":
            _CUM["readback"] += span.run_s
            mode["readback"] += span.run_s
    _M_SPANS.labels(kind=span.kind).inc()
    if span.category == "dispatch":
        _M_LOCK_WAIT.labels(kind=span.kind).observe(
            span.lock_wait_s, trace_id=None
        )


def record_span(
    kind: str,
    *,
    t_wall: float,
    lock_wait_s: float,
    run_s: float,
    rows: int = 0,
    tokens: int = 0,
    steps: int = 1,
    path: Optional[str] = None,
    rids: Sequence[int] = (),
    counters: Optional[Dict[str, int]] = None,
    handle: Any = None,
) -> Optional[Span]:
    """One compiled-program launch: ``t_wall`` is the wall clock at
    which the lock was requested, ``lock_wait_s`` the dispatch-lock
    wait, ``run_s`` the host time inside the lock (the view's
    ``enqueue_s``: on a TPU the enqueue call returns early). ``handle``
    is an output of the launch that no program donates: the watcher
    awaits it and stamps the completion fields. ``counters`` are extra
    counts of this kind of launch, shown as fields of the span's view.
    jit work this thread did since its last span (``note_jit``) is
    stamped on this one."""
    if not _ENABLED:
        return None
    span = Span(
        kind, "dispatch", threading.current_thread().name, t_wall,
        max(0.0, lock_wait_s), max(0.0, run_s), int(rows), int(tokens),
        max(1, int(steps)), path, tuple(rids)[:_RID_CAP], counters,
    )
    jit = getattr(_JIT_TLS, "pending", None)
    if jit is not None:
        _JIT_TLS.pending = None
        span.jit_s, span.jit_what = jit
    _append(span)
    if handle is not None:
        _PENDING.put((span, handle, _EPOCH))
    if _TRACE_ANNOTATION is not None:
        # one name on the profiler's host track, at the enqueue's return
        # (t_enq) on this thread: a capture shows the engine's spans on
        # its own clock and joins XLA Modules to spans by seq (seq exists
        # only now, so this marks the enqueue's end and does not wrap it)
        with _TRACE_ANNOTATION(f"{kind}#{span.seq}"):
            pass
    return span


def _record(kind: str, category: str, duration_s: float, rows: int = 0,
            rids: Sequence[int] = (), counters=None) -> None:
    """A host-side fact that ended now and lasted ``duration_s``."""
    _append(Span(
        kind, category, threading.current_thread().name,
        time.time() - duration_s, 0.0, float(duration_s), int(rows),
        rids=tuple(rids)[:_RID_CAP], counters=counters,
    ))


def record_stall(
    kind: str, duration_s: float, rids: Sequence[int] = ()
) -> None:
    """A named host stall on a tier thread (disagg handoff
    backpressure, transfer-queue waits): its own span on the thread's
    track. What it cost the device shows as the next launch's
    ``starved_s``."""
    if _ENABLED and duration_s > 0:
        _record(kind, "stall", duration_s, rids=rids)


def record_readback(kind: str, stall_s: float) -> None:
    """A device→host sync stall (reader thread, or the spec paths'
    on-thread syncs), attributed to the readback bubble component."""
    if _ENABLED and stall_s >= 0:
        _record(f"readback:{kind}", "readback", stall_s)


def record_pipeline_flush(stall_s: float, rows: int = 0) -> None:
    """The spec pipeline's deferred packed readback landing: the wait
    the dispatch thread actually paid when it finally synced a verify
    dispatched one round earlier (engine/llm_engine.py
    ``_flush_spec_pipeline``). Readback category — it IS the spec
    readback, shrunk by whatever host work overlapped the in-flight
    verify — under its own ``pipeline_flush`` kind so the before/after
    of the async pipeline is visible in the ring, not just the sums."""
    if _ENABLED and stall_s >= 0:
        _record("pipeline_flush", "readback", stall_s, rows)


def record_rollback(
    duration_s: float, rows: int = 0, rids: Sequence[int] = ()
) -> None:
    """An optimistic-draft rollback: verify readback contradicted the
    acceptance assumption the runahead draft was proposed under, and
    the dispatch thread re-proposed from the true context. Stall
    category with its own ``rollback`` kind; ``rows`` counts the
    rolled-back rows in the round."""
    if _ENABLED and duration_s >= 0:
        _record("rollback", "stall", duration_s, rows, rids)


def record_compile(program: str, seconds: float, hot: bool = False) -> None:
    """jit work inside an engine program (engine/compile_watch.py) as a
    timeline marker. The time already lands inside its dispatch span's
    ``enqueue_s``, so compile spans are overlay-only: excluded from the
    bubble sums."""
    if _ENABLED:
        _record(("hot_compile:" if hot else "compile:") + program,
                "compile", seconds)


# --------------------------------------------------------------------------- #
# jit events (fed by engine/compile_watch.py's listeners)

_JIT_TLS = threading.local()  # .pending: (seconds, what) since the last span
_JIT_RANK = {None: 0, "trace": 1, "cache_load": 2, "compile": 3}
# (t_end, program, what, seconds) of the newest jit work in engine
# programs, for a hold's record
_JIT_EVENTS: Deque[Tuple[float, str, str, float]] = deque(maxlen=64)


def note_jit(program: str, what: str, seconds: float) -> None:
    """jit traced, lowered, compiled or loaded an executable inside the
    engine program ``program`` on this thread, ending now: kept for the
    span this thread records next (the one it happened in, or the one an
    unspanned program is charged to) and for a hold's record."""
    if not _ENABLED:
        return
    _JIT_EVENTS.append((time.time(), program, what, round(seconds, 6)))
    old_s, old_what = getattr(_JIT_TLS, "pending", None) or (0.0, None)
    if _JIT_RANK[what] < _JIT_RANK[old_what]:
        what = old_what
    _JIT_TLS.pending = (old_s + seconds, what)


# --------------------------------------------------------------------------- #
# The completion stamp: ONE thread awaits the launches' outputs in
# enqueue order. It does nothing else — the reader's own work between
# two readbacks (a slab of 64 streams is ~5 ms) would make stamps late.

_PENDING: "queue.SimpleQueue[Tuple[Span, Any, int]]" = queue.SimpleQueue()
_STOP = object()  # stop_watcher() queues it: the watcher thread ends
_EPOCH = 0  # reset() bumps it: a launch awaited across a reset is not stamped
_WATCHER: Optional[threading.Thread] = None  # guarded by _LOCK
_STAMPED = threading.Condition(_LOCK)  # _stamp notifies it: clock_at
_TRACE_ANNOTATION = None  # jax.profiler.TraceAnnotation once a watcher started
_PREV_DONE: Optional[float] = None  # watcher-owned: the last stamp
_PREV_CPU = 0.0  # watcher-owned: process CPU seconds at the last stamp
_PREV_GC = 0  # watcher-owned: gc pauses counted at the last stamp
_RECENT: Deque[Span] = deque(maxlen=3)  # watcher-owned: the last launches
_RUNG_S: Dict[tuple, Deque[float]] = {}  # watcher-owned: device_s by rung
_GC_PAUSES = 0
# (t, gap) of heartbeats that came late, newest last
_LATE_BEATS: Deque[Tuple[float, float]] = deque(maxlen=256)


def _count_gc(phase: str, info: dict) -> None:
    global _GC_PAUSES
    if phase == "stop":
        _GC_PAUSES += 1


def _heartbeat() -> None:
    """Sleeps HEARTBEAT_S at a time and keeps the beats that came late:
    a hold during which the HOST stood still shows here."""
    last = time.time()
    while True:
        time.sleep(HEARTBEAT_S)
        now = time.time()
        if now - last > 2 * HEARTBEAT_S:
            _LATE_BEATS.append((now, now - last))
        last = now


def _await_and_stamp(span: "Span", handle: Any, epoch: int, now=time.time) -> None:
    try:
        handle.block_until_ready()
    except Exception:  # noqa: BLE001 - a deleted or failed output is ready
        pass
    if epoch == _EPOCH:
        _stamp(span, now())


def _watch() -> None:
    while True:
        item = _PENDING.get()
        if item is _STOP:
            return
        span, handle, epoch = item
        try:
            _await_and_stamp(span, handle, epoch)
        except Exception:  # noqa: BLE001 - the one thread that stamps must live
            logger.exception("dispatch watcher: stamping span %d failed", span.seq)


def start_watcher() -> None:
    """Start the watcher and its heartbeat (idempotent; the engine calls
    it at init when the timeline is on)."""
    global _WATCHER, _TRACE_ANNOTATION
    with _LOCK:
        if _WATCHER is not None or not _ENABLED:
            return
        watcher = _WATCHER = threading.Thread(
            target=_watch, daemon=True, name="llm-dispatch-watcher"
        )
    if _TRACE_ANNOTATION is None:
        try:
            from jax.profiler import TraceAnnotation as _TRACE_ANNOTATION
        except ImportError:  # a jax without the profiler: spans only
            pass
    if _count_gc not in gc.callbacks:  # the first start of this process: the hook and the heartbeat live on
        gc.callbacks.append(_count_gc)
        threading.Thread(
            target=_heartbeat, daemon=True, name="llm-dispatch-heartbeat"
        ).start()
    watcher.start()


def stop_watcher(timeout: float = 5.0) -> None:
    """End the watcher thread (tests only): a process that has built an
    engine keeps one for good, and it would race ``stamp_pending`` for
    the queue and stamp a fake-clock test's launches from the wall
    clock. The next ``start_watcher`` starts a new one."""
    global _WATCHER
    with _LOCK:
        watcher, _WATCHER = _WATCHER, None
    if watcher is not None and watcher.is_alive():
        _PENDING.put(_STOP)
        watcher.join(timeout)


def stamp_pending(now=time.time) -> int:
    """Await and stamp every queued launch on the calling thread (tests,
    and a process that never started the watcher). ``now`` is read after
    each output is ready."""
    n = 0
    while True:
        try:
            item = _PENDING.get_nowait()
        except queue.Empty:
            return n
        if item is _STOP:  # a stopped watcher never took it
            continue
        _await_and_stamp(*item, now)
        n += 1


def _rung(span: Span) -> tuple:
    c = span.counters or {}
    return (span.kind, c.get("rows_dispatched", span.rows),
            c.get("width", 0), span.steps)


def _far_longer(seconds: float, history: Deque[float]) -> bool:
    """Whether ``seconds`` passes BOTH hold constants against the running
    sample ``history``, which then takes it in."""
    far = (
        seconds > HOLD_MIN_S
        and len(history) >= _HOLD_MIN_SAMPLES
        and seconds > HOLD_TIMES_MEDIAN * statistics.median(history)
    )
    history.append(seconds)
    return far


def _stamp(span: Span, t_done: float) -> None:
    """The output of ``span``'s launch was ready at ``t_done``."""
    global _PREV_DONE, _PREV_CPU, _PREV_GC, _CLOCK
    t_enq = span.t_enq
    prev = t_enq if _PREV_DONE is None else _PREV_DONE
    t_done = max(t_done, prev)  # a stamp out of order: nothing is negative
    device_s = t_done - max(prev, t_enq)
    starved_s = max(0.0, t_enq - prev)
    cpu, pauses = time.process_time(), _GC_PAUSES
    held = _far_longer(
        device_s, _RUNG_S.setdefault(_rung(span), deque(maxlen=_HOLD_HISTORY))
    )
    with _LOCK:
        span.device_s = device_s
        span.starved_s = starved_s
        span.queued_s = max(0.0, prev - t_enq)
        mode = _CUM_MODE[_mode_of(span.kind)]
        _CUM["device"] += device_s
        _CUM["gap"] += starved_s
        mode["device"] += device_s
        mode["gap"] += starved_s
        span.clock = _CLOCK = DeviceClock(
            _CUM_MODE["decode"]["device"], _CUM_MODE["prefill"]["device"],
            _CUM_MODE["spec"]["device"] + _CUM_MODE["other"]["device"],
            _CUM["gap"], _CLOCK.launches + 1, span.seq,
        )
        span.t_done = t_done
        _STAMPED.notify_all()
    _M_DEVICE.labels(program=span.kind).observe(device_s, trace_id=None)
    _M_GAP.observe(starved_s, trace_id=None)
    _M_STARVED.inc(starved_s)
    if held:
        _hold_record(span, max(prev, t_enq), cpu - _PREV_CPU, pauses - _PREV_GC)
    _RECENT.append(span)
    _PREV_DONE, _PREV_CPU, _PREV_GC = t_done, cpu, pauses


def _rung_view(s: Span) -> Dict[str, Any]:
    kind, rows, width, steps = _rung(s)
    return {"seq": s.seq, "kind": kind, "rows_dispatched": rows,
            "width": width, "steps": steps}


def _stall_record(kind: str, t0: float, duration_s: float, rows: int,
                  record: Dict[str, Any], what: str) -> None:
    """ONE stall span and one log line for something that took far
    longer than it ever does (a launch the device held, a stream's gap)."""
    _append(Span(
        kind, "stall", threading.current_thread().name,
        t0, 0.0, duration_s, rows, counters=record,
    ))
    logger.warning("%s: %s", what, record)


def _hold_record(span: Span, t0: float, cpu_s: float, gc_pauses: int) -> None:
    """A launch the device held: what it was, what was enqueued before
    it, and what the process knows of the interval (jit, the host's
    heartbeat, CPU against wall, gc, device memory)."""
    bytes_in_use = None
    try:
        import jax

        stats = jax.local_devices()[0].memory_stats() or {}
        bytes_in_use = stats.get("bytes_in_use")
    except Exception:  # noqa: BLE001 - a backend without memory_stats
        pass
    record = {
        "held": dict(_rung_view(span), device_s=round(span.device_s, 6)),
        "before": [_rung_view(s) for s in _RECENT],
        "jit": [e[1:] for e in _JIT_EVENTS if t0 <= e[0] <= span.t_done],
        "heartbeat_max_gap_s": round(max(
            (g for t, g in _LATE_BEATS if t0 <= t <= span.t_done + HEARTBEAT_S),
            default=HEARTBEAT_S,
        ), 6),
        "cpu_s": round(cpu_s, 6),
        "wall_s": round(span.t_done - (_PREV_DONE or t0), 6),
        "gc_pauses": gc_pauses,
        "bytes_in_use": bytes_in_use,
    }
    _stall_record(f"device_hold:{span.kind}", t0, span.device_s, span.rows,
                  record, "DEVICE HOLD")


# --------------------------------------------------------------------------- #
# What a token waited behind: a stream hand-off's gap, split by the
# device clock (the engine's reader thread drives this; docs/streaming.md)

_STAMP_WAIT_S = 0.05  # the longest a reader waits for its own launch's stamp
_GAP_S: Deque[float] = deque(maxlen=_HOLD_HISTORY)  # reader-owned: each block's longest gap
_GAP_LAUNCH_CAP = 32  # launches a stream_gap record lists (the newest)


def device_clock() -> DeviceClock:
    """The device's seconds by kind over every launch stamped so far."""
    return _CLOCK


def clock_at(span: Optional[Span]) -> DeviceClock:
    """The device clock as of ``span``'s launch: every launch the device
    finished up to and including it. The watcher and a reader wake on
    the same output, so a reader that comes first WAITS for the stamp: a
    clock taken before it would count the launch into its rows' NEXT
    gaps. Bounded by ``_STAMP_WAIT_S`` (a launch recorded before a
    ``reset`` is never stamped; a watcher starved of the interpreter):
    past it, and without a span or a watcher, the clock as it stands; the
    launch then lands in the next gap and the sums stay conserved."""
    if span is None:
        return _CLOCK
    if span.clock is None:
        with _LOCK:  # the lock _STAMPED is a condition over
            if _WATCHER is not None:
                _STAMPED.wait_for(lambda: span.clock is not None, _STAMP_WAIT_S)
    return span.clock or _CLOCK


class HandoffBlock:
    """The stream hand-offs of ONE readback, on the reader thread: each
    request's gap since its previous hand-off is split into the device's
    time by kind (the device clock now minus the clock the request kept
    then) and the host's rest. Requests that were last handed tokens by
    the same launch share one clock OBJECT, so the difference is taken
    once per distinct previous clock, not once per row. ``close`` writes
    the block's row count and its LONGEST gap's parts into the span's
    fields and the sums into ``/metrics``; nothing per row is kept."""

    __slots__ = ("clock", "rows", "_fields", "_parts", "_sums", "_longest")

    def __init__(self, span: Optional[Span] = None):
        self.clock = clock_at(span)
        # the span's fields, where its dispatch wrote the GAP_FIELDS keys
        # (a value lands only on a key that is there: the dict keeps its
        # size under a concurrent scrape; a prefill chunk's has none)
        fields = span.counters if span is not None else None
        self._fields = fields if fields and "gap_s" in fields else None
        self.rows = 0
        self._parts: Dict[int, Tuple[float, float, float, float]] = {}
        self._sums = [0.0, 0.0, 0.0, 0.0, 0.0]
        self._longest: Optional[tuple] = None

    def handoff(self, req, now: float) -> None:
        """One request's hand-off at ``now``. ``req`` keeps, between its
        hand-offs, ``gap_clock`` (the clock of its previous one; None
        before its first, which only starts its first gap),
        ``gap_owed`` and ``t_last_token`` (that hand-off's time, which
        the caller advances). The host's part is the gap minus the
        device's four, floored at 0; what the floor cut (the previous
        hand-off came late, so its gap already held this much of the
        device's time as host time) is owed by the request's next gaps,
        so over a stream every part sums to every gap."""
        prev, req.gap_clock = req.gap_clock, self.clock
        self.rows += 1
        if prev is None:
            return
        clock = self.clock
        parts = self._parts.get(id(prev))
        if parts is None:
            parts = self._parts[id(prev)] = (
                clock.decode_s - prev.decode_s, clock.extend_s - prev.extend_s,
                clock.other_s - prev.other_s, clock.starved_s - prev.starved_s,
            )
        gap_s = now - req.t_last_token
        rest = gap_s - (parts[0] + parts[1] + parts[2] + parts[3]) + req.gap_owed
        host_s, req.gap_owed = (rest, 0.0) if rest > 0 else (0.0, rest)
        _M_HANDOFF_GAP.observe(gap_s, trace_id=None)
        sums = self._sums
        sums[0] += parts[0]
        sums[1] += parts[1]
        sums[2] += parts[2]
        sums[3] += parts[3]
        sums[4] += host_s
        if self._longest is None or gap_s > self._longest[0]:
            self._longest = (gap_s, parts, host_s, prev, req.rid)

    def close(self) -> None:
        fields = self._fields
        if self._longest is None:
            if fields is not None:
                fields["handoff_rows"] = self.rows
            return
        for part, seconds in zip(_M_GAP_PARTS, self._sums):
            part.inc(seconds)
        gap_s, parts, host_s, prev, rid = self._longest
        record = {
            "handoff_rows": self.rows,
            "gap_s": round(gap_s, 6),
            "gap_decode_s": round(parts[0], 6),
            "gap_extend_s": round(parts[1], 6),
            "gap_other_s": round(parts[2], 6),
            "gap_starved_s": round(parts[3], 6),
            "gap_host_s": round(host_s, 6),
            "gap_launches": self.clock.launches - prev.launches,
        }
        if fields is not None:
            fields.update(record)  # one call: a scrape sees none or all of a block's values
        if _far_longer(gap_s, _GAP_S):
            with _LOCK:
                launches = [
                    dict(_rung_view(s), device_s=round(s.device_s, 6))
                    for s in _SPANS
                    if s.category == "dispatch" and s.t_done is not None
                    and prev.seq < s.seq <= self.clock.seq
                ]
            record["launches"] = launches[-_GAP_LAUNCH_CAP:]
            _stall_record("stream_gap", time.time() - gap_s, gap_s, self.rows,
                          dict(record, rid=rid), "STREAM GAP")


# --------------------------------------------------------------------------- #
# Views


def cursor() -> int:
    """The process span cursor — spans_since(cursor()) returns only
    spans recorded after this call (the scraper-anchor contract shared
    with flight_recorder.cursor())."""
    with _LOCK:
        return _SEQ


def spans_since(since: int, limit: int = 500) -> Tuple[List[Dict], int]:
    """Incremental tail: span views with ``seq > since``, oldest first,
    ``limit``-capped, plus the current cursor. Cursor 0 starts from the
    oldest retained span."""
    with _LOCK:
        out = [s.view() for s in _SPANS if s.seq > since][: int(limit)]
        return out, _SEQ


def recent_spans(limit: int = 256) -> List[Dict]:
    """Newest ``limit`` span views, newest first (the blackbox embed)."""
    with _LOCK:
        spans = list(_SPANS)[-int(limit):]
        return [s.view() for s in reversed(spans)]


def counters_snapshot() -> Dict[str, float]:
    """Cumulative component seconds for the engine's legacy flat
    ``metrics`` dict — the loadgen scraper deltas these over the run
    window to build the gated ``bubble`` summary block. ``device`` is the
    sum of ``device_s``, ``gap`` the sum of ``starved_s``."""
    with _LOCK:
        out = {
            "timeline_spans": _CUM["spans"],
            "timeline_device_seconds": round(_CUM["device"], 6),
            "timeline_lock_wait_seconds": round(_CUM["lock"], 6),
            "timeline_gap_seconds": round(_CUM["gap"], 6),
            "timeline_readback_stall_seconds": round(_CUM["readback"], 6),
        }
        # Per-mode split (always emitted, zeros included, so scraper
        # deltas never see a key appear mid-run): the mode sums equal
        # the totals above component by component.
        for mode, cum in _CUM_MODE.items():
            out[f"timeline_{mode}_device_seconds"] = round(cum["device"], 6)
            out[f"timeline_{mode}_lock_wait_seconds"] = round(cum["lock"], 6)
            out[f"timeline_{mode}_gap_seconds"] = round(cum["gap"], 6)
            out[f"timeline_{mode}_readback_stall_seconds"] = round(
                cum["readback"], 6
            )
            out[f"timeline_{mode}_dispatches"] = cum["dispatches"]
        return out


def bubble_snapshot(window_s: float = _BUBBLE_WINDOW_S) -> Dict[str, float]:
    """Rolling-window bubble decomposition. The denominator is
    engine-ACTIVE wall (device + lock + starved + readback seconds
    inside the window) — idle-with-no-work time is nobody's bubble — so
    the four component ratios sum to exactly 1.0."""
    horizon = time.time() - window_s
    busy = lock = gap = readback = 0.0
    gaps: List[float] = []
    mode_active = {m: 0.0 for m in MODES}
    n = 0
    with _LOCK:
        for s in _SPANS:
            if s.t_enq < horizon or s.category in ("compile", "stall"):
                continue
            n += 1
            if s.category == "dispatch":
                busy += s.device_s
                lock += s.lock_wait_s
                gap += s.starved_s
                gaps.append(s.starved_s)
                mode_active[_mode_of(s.kind)] += (
                    s.device_s + s.lock_wait_s + s.starved_s
                )
            else:
                readback += s.run_s
                mode_active[_mode_of(s.kind)] += s.run_s
    active = busy + lock + gap + readback
    if active <= 0:
        return {"bubble_spans_in_window": 0}
    ratio = lambda x: round(x / active, 4)  # noqa: E731
    ordered = sorted(gaps)
    gap_p95 = ordered[
        min(len(ordered) - 1, max(0, int(round(0.95 * (len(ordered) - 1)))))
    ] if ordered else 0.0
    out = {
        "bubble_ratio": ratio(active - busy),
        "bubble_device_ratio": ratio(busy),
        "bubble_lock_ratio": ratio(lock),
        "bubble_gap_ratio": ratio(gap),
        "bubble_readback_ratio": ratio(readback),
        "bubble_window_s": round(active, 4),
        "bubble_gap_p95_s": round(gap_p95, 6),
        "bubble_spans_in_window": n,
    }
    # Per-mode share of the active wall (all categories attributed to
    # the mode whose span produced them) — zero-activity modes are
    # omitted, the present ones sum to ~1.0 like the components do.
    for mode, secs in mode_active.items():
        if secs > 0:
            out[f"bubble_mode_{mode}_ratio"] = ratio(secs)
    return out


# --------------------------------------------------------------------------- #
# Perfetto (Chrome trace JSON) export

_PID_HOST = 1
_PID_DEVICE = 2
_PID_DEVICE_XPLANE = 3
_TID_REQUESTS = 1_000_000  # flight-recorder overlay track


def perfetto_trace(
    spans: Sequence[Dict],
    flight: Sequence[Dict] = (),
    device_events: Sequence[Dict] = (),
) -> Dict[str, Any]:
    """Chrome-trace JSON over span VIEWS (spans_since/recent_spans
    output): one track per tier thread on the host process, a device
    track (each launch from ``t_done - device_s`` to ``t_done``; replaced
    by xplane events when ``device_events`` is given), and flight-recorder request
    lifecycles overlaid as instants carrying their trace ids — the join
    key to stitched router traces. Timestamps are absolute wall-clock
    microseconds, so traces from co-scraped processes align."""
    events: List[Dict[str, Any]] = [
        {"ph": "M", "pid": _PID_HOST, "name": "process_name",
         "args": {"name": "genai-engine host"}},
        {"ph": "M", "pid": _PID_HOST, "tid": _TID_REQUESTS,
         "name": "thread_name", "args": {"name": "requests"}},
    ]
    tids: Dict[str, int] = {}

    def tid_for(thread: str) -> int:
        tid = tids.get(thread)
        if tid is None:
            tid = tids[thread] = len(tids) + 1
            events.append(
                {"ph": "M", "pid": _PID_HOST, "tid": tid,
                 "name": "thread_name", "args": {"name": thread}}
            )
        return tid

    emitted_device = False
    for view in sorted(spans, key=lambda v: v.get("t_wall", 0.0)):
        thread = view.get("thread", "?")
        tid = tid_for(thread)
        t0 = float(view.get("t_wall", 0.0))
        lock_wait = float(view.get("lock_wait_s", 0.0))
        run = float(view.get("enqueue_s", view.get("duration_s", 0.0)))
        args = {
            k: view[k]
            for k in ("seq", "rows", "tokens", "steps", "path", "rids",
                      "starved_s", "queued_s", "jit_what", "category")
            if k in view
        }
        if lock_wait > 0:
            events.append(
                {"ph": "X", "pid": _PID_HOST, "tid": tid,
                 "name": "dispatch_lock_wait", "cat": "lock",
                 "ts": t0 * 1e6, "dur": lock_wait * 1e6,
                 "args": {"seq": view.get("seq")}}
            )
        events.append(
            {"ph": "X", "pid": _PID_HOST, "tid": tid,
             "name": view.get("kind", "?"),
             "cat": view.get("category", "dispatch"),
             "ts": (t0 + lock_wait) * 1e6, "dur": run * 1e6,
             "args": args}
        )
        if "t_done" in view and not device_events:
            emitted_device = True
            events.append(
                {"ph": "X", "pid": _PID_DEVICE, "tid": 1,
                 "name": view.get("kind", "?"), "cat": "device",
                 "ts": (view["t_done"] - view["device_s"]) * 1e6,
                 "dur": view["device_s"] * 1e6,
                 "args": {"seq": view.get("seq")}}
            )
    if emitted_device:
        events.append(
            {"ph": "M", "pid": _PID_DEVICE, "name": "process_name",
             "args": {"name": "device (completion stamps)"}}
        )
    if device_events:
        events.append(
            {"ph": "M", "pid": _PID_DEVICE_XPLANE, "name": "process_name",
             "args": {"name": "device (xplane)"}}
        )
        for ev in device_events:
            events.append(
                {"ph": "X", "pid": _PID_DEVICE_XPLANE,
                 "tid": int(ev.get("tid", 1)),
                 "name": ev.get("name", "?"), "cat": "device",
                 "ts": float(ev.get("ts_us", 0.0)),
                 "dur": float(ev.get("dur_us", 0.0)),
                 "args": {}}
            )
    for tl in flight or ():
        base = float(tl.get("started_at", 0.0))
        if not base:
            continue
        ident = {
            "request_id": tl.get("request_id"),
            "trace_id": tl.get("trace_id"),
            "rids": tl.get("rids"),
        }
        for ev in tl.get("timeline", ()):
            events.append(
                {"ph": "i", "s": "p", "pid": _PID_HOST,
                 "tid": _TID_REQUESTS, "name": ev.get("event", "?"),
                 "cat": "request",
                 "ts": (base + float(ev.get("t_s", 0.0))) * 1e6,
                 "args": ident}
            )
    return {"traceEvents": events, "displayTimeUnit": "ms"}


# --------------------------------------------------------------------------- #
# Test hook


def reset() -> None:
    """Drop every span, queued stamp and rung history and rewind the
    cursor/counters (tests only)."""
    global _SEQ, _PREV_DONE, _EPOCH, _CLOCK
    _EPOCH += 1
    while not _PENDING.empty():
        _PENDING.get_nowait()
    _PREV_DONE = None
    _RECENT.clear()
    _RUNG_S.clear()
    _JIT_EVENTS.clear()
    _JIT_TLS.pending = None
    _GAP_S.clear()
    with _LOCK:
        _SPANS.clear()
        _SEQ = 0
        _CLOCK = DeviceClock()
        for k in _CUM:
            _CUM[k] = 0.0
        for cum in _CUM_MODE.values():
            for k in cum:
                cum[k] = 0.0
