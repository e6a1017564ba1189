"""Per-layer metrics read from the dispatch timeline's completion stamp.

Since PR 40 a dispatch span carries ``t_done`` (the wall time at which an
output of its launch was ready, stamped by the program's watcher thread)
and from it ``device_s``, ``starved_s`` and ``queued_s``
(``generativeaiexamples_tpu/engine/dispatch_timeline.py``). These readers
take a statistic of such a field over the spans of the WHOLE window, where
the device readers of ``readers.py`` see the few traced seconds. A metric
file names them as ``"perfbench.span_readers:<function>"``; both have the
``(ctx, params)`` signature of ``readers.py``. On a program whose spans do
not carry the field (the parent of PR 40, or ``GENAI_DISPATCH_TIMELINE=off``)
they return None and the line leaves the metric out.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional

from perfbench import reduce


def _matches(span: Dict[str, Any], where: Dict[str, Any]) -> bool:
    """``{"width": 512}``: the field equals the value;
    ``{"width_below": 512}``: the field is there and below it."""
    for key, want in where.items():
        field, below = (key[: -len("_below")], True) if key.endswith("_below") else (key, False)
        if field not in span or (span[field] >= want if below else span[field] != want):
            return False
    return True


def _values(ctx, p) -> List[float]:
    """``field`` (over ``per``, if given) of the dispatch spans of
    ``kinds`` (all kinds if absent) that carry it and match ``where``. A
    ``where`` value of the form ``"engine:<key>"`` is that engine setting
    of the configuration."""
    engine = ctx["config"].get("engine", {})
    where = {
        k: engine[v.split(":", 1)[1]] if isinstance(v, str) and v.startswith("engine:") else v
        for k, v in p.get("where", {}).items()
    }
    kinds, field, per = p.get("kinds"), p["field"], p.get("per")
    return [
        float(s[field]) / (float(s[per]) if per else 1.0)
        for s in ctx["spans"]
        if s.get("category", "dispatch") == "dispatch" and field in s
        and (kinds is None or s.get("kind") in kinds)
        and (per is None or s.get(per)) and _matches(s, where)
    ]


def span_stat(ctx, p) -> Optional[float]:
    """``stat`` ("mean", "max", "sum" or "p<q>") of ``field`` over the
    selected spans, times ``scale``; with ``over_window`` the result is
    divided by the window's seconds (a sum becomes a share of the
    window). None where no selected span carries the field."""
    vals = _values(ctx, p)
    if not vals:
        return None
    stat = p.get("stat", "p50")
    if stat == "mean":
        out = sum(vals) / len(vals)
    elif stat in ("max", "sum"):
        out = max(vals) if stat == "max" else sum(vals)
    else:
        out = reduce.percentile(vals, float(stat[1:]))
    if p.get("over_window"):
        t0, t1 = ctx["window"]
        out /= (t1 - t0)
    return out * float(p.get("scale", 1.0))


def span_share(ctx, p) -> Optional[float]:
    """The sum of ``field`` over the dispatch spans of ``kinds`` over its
    sum over every dispatch span that carries it, percent."""
    whole = sum(_values(ctx, {"field": p["field"]}))
    if not whole:
        return None
    return 100.0 * sum(_values(ctx, p)) / whole
