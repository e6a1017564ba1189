"""Arithmetic from the client's frame log to the end-to-end metrics.

Pure functions over plain lists so that the tests can feed hand-made
logs. A request here is the dict ``RequestLog.to_json`` writes: times in
seconds relative to one origin, ``frames_s`` the arrival of every
content frame (one frame is one answer token, see the configuration's
``assumed``), ``send_s``/``due_s``/``end_s`` and ``status``.

What each end-to-end metric measures is fixed here and nowhere else:

- ``out_tok_s``: content frames that ARRIVED inside the window over the
  window's seconds — all the work and all the time of the window, not a
  sum over completed requests.
- ``ttft_*``: first content frame minus the send (open loop: minus the
  due instant), over every request whose first frame arrived inside the
  window.
- ``itl_p99_ms`` / ``itl_p995_ms``: gaps between consecutive content
  frames of one stream, over every gap whose later frame arrived inside
  the window; the 99th and the 99.5th percentile of all of them.
"""
from __future__ import annotations

import math
from collections import Counter
from typing import Any, Dict, Iterable, List, Optional, Sequence


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """Linear interpolation between order statistics (numpy's default).
    None for an empty sample."""
    if not values:
        return None
    xs = sorted(values)
    if len(xs) == 1:
        return float(xs[0])
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return float(xs[lo] + (xs[hi] - xs[lo]) * (pos - lo))


def in_window(t: Optional[float], t0: float, t1: float) -> bool:
    return t is not None and t0 <= t < t1


def window_tokens(requests: Iterable[Dict[str, Any]], t0: float, t1: float) -> int:
    return sum(1 for r in requests for t in r["frames_s"] if t0 <= t < t1)


def out_tok_s(requests: Iterable[Dict[str, Any]], t0: float, t1: float) -> float:
    return window_tokens(requests, t0, t1) / (t1 - t0)


def sub_window_rates(requests: Sequence[Dict[str, Any]], t0: float, t1: float, parts: int) -> List[float]:
    """Tokens per second in each of ``parts`` equal slices of the window."""
    step = (t1 - t0) / parts
    counts = [0] * parts
    for r in requests:
        for t in r["frames_s"]:
            if t0 <= t < t1:
                counts[min(parts - 1, int((t - t0) / step))] += 1
    return [c / step for c in counts]


def start_of(r: Dict[str, Any]) -> float:
    """The instant a request is timed from: due (open loop) or send."""
    return r["due_s"] if r.get("due_s") is not None else r["send_s"]


def ttfts_ms(requests: Iterable[Dict[str, Any]], t0: float, t1: float) -> List[float]:
    return [
        (r["frames_s"][0] - start_of(r)) * 1000.0
        for r in requests
        if r["frames_s"] and t0 <= r["frames_s"][0] < t1
    ]


def gaps_ms(requests: Iterable[Dict[str, Any]], t0: float, t1: float) -> List[float]:
    out = []
    for r in requests:
        f = r["frames_s"]
        out.extend((b - a) * 1000.0 for a, b in zip(f, f[1:]) if t0 <= b < t1)
    return out


def finished_in(requests: Iterable[Dict[str, Any]], t0: float, t1: float) -> List[Dict[str, Any]]:
    """Requests that ended (answered or failed) inside the window."""
    return [r for r in requests if r["status"] != "in_flight" and in_window(r["end_s"], t0, t1)]


def tpots_ms(requests: Iterable[Dict[str, Any]], t0: float, t1: float) -> List[float]:
    """Per answered request: (last frame - first frame) / (tokens - 1)."""
    out = []
    for r in finished_in(requests, t0, t1):
        f = r["frames_s"]
        if r["status"] == "ok" and len(f) >= 2:
            out.append((f[-1] - f[0]) * 1000.0 / (len(f) - 1))
    return out


def end_to_end(requests: Sequence[Dict[str, Any]], t0: float, t1: float) -> Dict[str, Optional[float]]:
    """Every end-to-end metric the client can compute; the manifest
    decides which of them a cell reports."""
    tt = ttfts_ms(requests, t0, t1)
    gaps = gaps_ms(requests, t0, t1)
    return {
        "out_tok_s": out_tok_s(requests, t0, t1),
        "ttft_p50_ms": percentile(tt, 50),
        "ttft_p90_ms": percentile(tt, 90),
        "itl_p99_ms": percentile(gaps, 99),
        "itl_p995_ms": percentile(gaps, 99.5),
    }


def counts(requests: Sequence[Dict[str, Any]], t0: float, t1: float) -> Dict[str, int]:
    done = finished_in(requests, t0, t1)
    return {
        "attempted": len(done),
        "failed": sum(1 for r in done if r["status"] != "ok"),
    }


def unpaired_counts(delivered: Sequence[int], generated: Sequence[int]) -> List[int]:
    """Tokens delivered per request (client) against tokens generated per
    request (engine), as multisets: a request that ends at an edge of the
    window may be counted by one side only, so the side that counted
    fewer must find a partner for every one of its counts in the other.
    Returns the counts left without a partner (empty = they agree)."""
    few, many = sorted((Counter(delivered), Counter(generated)), key=lambda c: sum(c.values()))
    return sorted((few - many).elements())


def spread(values: Sequence[float]) -> Optional[float]:
    """Interquartile range over the median, as the driver reads a set."""
    import statistics

    if len(values) < 2:
        return None
    q = statistics.quantiles(values, n=4)
    med = statistics.median(values)
    return (q[2] - q[0]) / med if med else None
