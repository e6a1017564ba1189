"""Per-layer metrics read from what a stream hand-off's gap was made of.

Since PR 54 the span of a launch that handed tokens to streams (kinds
``decode``, ``spec``, ``spec_block``) carries ``handoff_rows`` (the rows
it handed tokens to) and, of the row of that block whose gap since its
previous hand-off was LONGEST, ``gap_s`` and its split: the device's time
by program kind (``gap_decode_s``, ``gap_extend_s``, ``gap_other_s``,
``gap_starved_s``) and the host's rest (``gap_host_s``), with
``gap_launches`` (``generativeaiexamples_tpu/engine/dispatch_timeline.py``
``HandoffBlock``). The rows of one block wait behind the same launches, so
a span stands for ``handoff_rows`` gaps of its ``gap_s``.

A stream's frames arrive a readback block at a time, so the client's
``frame_q``-th percentile over ALL frame gaps is the percentile
``100 - (100 - frame_q) * frames a hand-off`` of the gaps BETWEEN
hand-offs (PERF.md section 2): the 96th where ``decode_block`` is 8, the
99th where it is 2. The reader here takes the spans (of a traced run:
those outside the traced stretch) at or above that percentile and says what share of their gaps one part was
(``gap_tail_share``; ``gap_tail_ms`` is the percentile itself). A metric
file names it as ``"perfbench.gap_readers:gap_tail_share"``;
``(ctx, params)`` as in ``readers.py``. On a program whose spans do not
carry the field (the parent of PR 54, or ``GENAI_DISPATCH_TIMELINE=off``)
it returns None and the line leaves the metric out.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple


def handoff_percentile(ctx, p) -> Optional[float]:
    """The percentile of the gaps between hand-offs that the client's
    ``frame_q``-th percentile of all frame gaps reads, from the
    configuration's ``engine.decode_block`` frames a hand-off."""
    frames = ctx["config"].get("engine", {}).get("decode_block")
    if not frames:
        return None
    return 100.0 - (100.0 - float(p.get("frame_q", 99.5))) * float(frames)


# The profiler's start holds the host for 0.05-0.1 s just before the traced
# stretch's first device event (PERF.md section 6, PR 40 and PR 54): with
# decode_block 2 that one gap is a fifth of a 20 s window's top percent.
TRACE_START_MARGIN_S = 0.5


def gap_spans(ctx, p) -> List[Dict[str, Any]]:
    """The window's dispatch spans of ``kinds`` that carry a gap: the
    field is there and at least one row of the block had one. Of a traced
    run, the spans OUTSIDE the traced stretch: the window's last
    ``trace.window_s`` seconds and the profiler's start before them are
    the harness's doing, not the system's."""
    kinds = p.get("kinds")
    spans = [
        s for s in ctx["spans"]
        if s.get("category", "dispatch") == "dispatch" and s.get("gap_s")
        and s.get("handoff_rows") and (kinds is None or s.get("kind") in kinds)
    ]
    traced_s = (ctx.get("trace") or {}).get("window_s")
    walls = [s["t_wall"] for s in ctx["spans"] if "t_wall" in s]
    if traced_s and walls:
        cut = max(walls) - float(traced_s) - TRACE_START_MARGIN_S
        spans = [s for s in spans if s.get("t_wall", cut - 1.0) < cut]
    return spans


def weighted_tail(spans: List[Dict[str, Any]], q: float) -> Tuple[Optional[float], List[Dict[str, Any]]]:
    """The ``q``-th percentile of ``gap_s`` over spans weighted by
    ``handoff_rows`` (the smallest gap at which the running weight reaches
    ``q`` percent of the whole), and the spans at or above it."""
    if not spans:
        return None, []
    ordered = sorted(spans, key=lambda s: s["gap_s"])
    want = sum(s["handoff_rows"] for s in ordered) * q / 100.0
    running = 0.0
    for s in ordered:
        running += s["handoff_rows"]
        if running >= want:
            cut = s["gap_s"]
            return float(cut), [t for t in ordered if t["gap_s"] >= cut]
    return float(ordered[-1]["gap_s"]), ordered[-1:]


def gap_tail_ms(ctx, p) -> Optional[float]:
    """The judged percentile of the gaps between hand-offs itself, ms: the
    program's own reading of the tail the client's ``frame_q`` reads."""
    q = handoff_percentile(ctx, p)
    cut, _ = weighted_tail(gap_spans(ctx, p), q) if q is not None else (None, [])
    return None if cut is None else cut * 1000.0


def gap_tail_share(ctx, p) -> Optional[float]:
    """Over the spans at or above the judged percentile of the gaps
    between hand-offs: ``part`` over ``gap_s``, both summed with each
    span's ``handoff_rows`` as its weight, percent."""
    q = handoff_percentile(ctx, p)
    if q is None:
        return None
    _, tail = weighted_tail(gap_spans(ctx, p), q)
    whole = sum(s["handoff_rows"] * s["gap_s"] for s in tail)
    if not whole:
        return None
    return 100.0 * sum(s["handoff_rows"] * s.get(p["part"], 0.0) for s in tail) / whole
