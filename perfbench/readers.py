"""Per-layer metrics: a handful of generic readers, chosen by name.

A per-layer metric is a small data file under ``perfbench/layer_metrics``
that names its reader and the reader's parameters: either one of the
generic readers below (``"reader": "span_mean"``, a key of ``READERS``)
or a function of any module of the benchmark, as
``"reader": "perfbench.arch.<module>:<function>"`` with the same
``(ctx, params)`` signature (``resolve``). A later PR adds a metric by
adding a file, and a new *kind* of source by adding a function to its
adapter; it touches nothing here. A reader that finds nothing to read
returns None and the harness leaves the metric out of the line.

The context every reader gets (``ctx``):

- ``requests``: the client's request logs (``RequestLog.to_json``),
  ``window``: (t0, t1) on the same clock;
- ``flight``: the server's flight-recorder timelines that finished in
  the window, ``pairs``: (client request, timeline) joined in send order;
- ``spans``: dispatch-timeline spans recorded in the window;
- ``metrics_before`` / ``metrics_after``: parsed ``/metrics`` text;
- ``trace``: ``trace_reduce.reduce_events`` summary of the traced
  interval, or None when the run was not traced;
- ``config``, ``adapter`` (the configuration's module under
  ``perfbench/arch``), ``peaks`` (this device's row of ``peaks.json``),
  and ``read(name)``: another metric's value (for a share over a time).
"""
from __future__ import annotations

import re
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from perfbench import arch, reduce, trace_reduce

Metrics = Dict[Tuple[str, frozenset], float]


def parse_metrics(text: str) -> Metrics:
    """Prometheus text exposition -> {(name, frozenset(labels)): value}."""
    out: Metrics = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        m = re.match(r"^([a-zA-Z_:][\w:]*)(\{[^}]*\})?\s+(\S+)", line)
        if not m:
            continue
        labels = frozenset(re.findall(r'(\w+)="([^"]*)"', m.group(2) or ""))
        try:
            out[(m.group(1), labels)] = float(m.group(3))
        except ValueError:
            pass
    return out


def metric_sum(metrics: Metrics, name: str, **labels: str) -> float:
    want = set(labels.items())
    return sum(v for (n, ls), v in metrics.items() if n == name and want <= set(ls))


def phases(timeline: Dict[str, Any]) -> Optional[Dict[str, float]]:
    """One flight-recorder timeline split into seconds per phase — the
    arithmetic of ``tools/loadgen/phases.py`` (copied): queue wait from
    ``admit.queue_wait_s``, prefill = admit -> first_token, decode =
    first_token -> the last decode_leave/engine_finish, retrieval = the
    sum of ``retrieve`` durations, batcher = the sum of coalescing waits."""
    t_submit = t_admit = t_first = t_end = None
    queue_wait = retrieval = batcher = 0.0
    for e in timeline.get("timeline") or []:
        name, t = e.get("event"), float(e.get("t_s", 0.0))
        if name == "submit" and t_submit is None:
            t_submit = t
        elif name == "admit":
            t_admit = t if t_admit is None else t_admit
            queue_wait += float(e.get("queue_wait_s", 0.0))
        elif name == "first_token" and t_first is None:
            t_first = t
        elif name in ("decode_leave", "engine_finish"):
            t_end = t
        elif name == "retrieve":
            retrieval += float(e.get("duration_s", 0.0))
        elif name == "batcher_coalesced":
            batcher += float(e.get("wait_ms", 0.0)) / 1000.0
    if t_submit is None or t_admit is None:
        return None
    if not queue_wait:
        queue_wait = max(0.0, t_admit - t_submit)
    prefill = max(0.0, t_first - t_admit) if t_first is not None else 0.0
    decode = max(0.0, t_end - t_first) if (t_first is not None and t_end is not None) else 0.0
    return {"queue_wait": queue_wait, "prefill": prefill, "decode": decode,
            "retrieval": retrieval, "batcher": batcher}


def event_attr(timeline: Dict[str, Any], event: str, field: str) -> Optional[float]:
    for e in timeline.get("timeline") or []:
        if e.get("event") == event and field in e:
            return float(e[field])
    return None


# --------------------------------------------------------------------------- #
# Readers: (ctx, params) -> value or None


def flight_phase_percentile(ctx, p) -> Optional[float]:
    """Percentile over requests of one phase of ``phases``, in ms."""
    vals = [ph[p["phase"]] * 1000.0 for ph in map(phases, ctx["flight"]) if ph]
    return reduce.percentile(vals, p.get("q", 50))


def client_other_percentile(ctx, p) -> Optional[float]:
    """Client total minus the server's phases, per joined request, ms:
    HTTP, SSE, chain glue and whatever no phase accounts for."""
    vals = []
    for req, tl in ctx["pairs"]:
        ph = phases(tl)
        if ph is None or req["end_s"] is None:
            continue
        total = req["end_s"] - reduce.start_of(req)
        vals.append(max(0.0, total - sum(ph.values())) * 1000.0)
    return reduce.percentile(vals, p.get("q", 50))


def client_tpot_percentile(ctx, p) -> Optional[float]:
    t0, t1 = ctx["window"]
    return reduce.percentile(reduce.tpots_ms(ctx["requests"], t0, t1), p.get("q", 50))


def client_gap_percentile(ctx, p) -> Optional[float]:
    """Percentile of the gaps between consecutive frames of one stream,
    over every gap whose later frame arrived inside the window, ms."""
    t0, t1 = ctx["window"]
    return reduce.percentile(reduce.gaps_ms(ctx["requests"], t0, t1), p["q"])


def span_mean(ctx, p) -> Optional[float]:
    """Mean of one field over dispatch spans of one kind (a count)."""
    vals = [
        float(s[p["field"]]) for s in ctx["spans"]
        if s.get("kind") == p["kind"] and s.get("category", "dispatch") == p.get("category", "dispatch")
        and p["field"] in s
    ]
    return sum(vals) / len(vals) if vals else None


def device_module_ms(ctx, p) -> Optional[float]:
    """Mean device time of one execution of the programs matching
    ``match``, in ms, divided by an engine setting (steps per program)."""
    tr = ctx["trace"]
    if not tr or not tr.get("devices"):
        return None
    rx = re.compile(p["match"])
    hits = [m for name, m in tr["modules"].items() if rx.search(name)]
    count = sum(m["count"] for m in hits)
    if not count:
        return None
    per = sum(m["total_s"] for m in hits) / count
    return per * 1000.0 / float(ctx["config"]["engine"].get(p.get("divide_by_engine", ""), 1) or 1)


def device_op_busy_share(ctx, p) -> Optional[float]:
    """Self time of the operations matching ``match`` over device-busy
    time of the traced interval, percent."""
    tr = ctx["trace"]
    if not tr or not tr.get("devices") or not tr["busy_s"]:
        return None
    return 100.0 * trace_reduce.matching_s(tr["ops_self_s"], p["match"]) / tr["busy_s"]


def device_idle_share(ctx, p) -> Optional[float]:
    tr = ctx["trace"]
    if not tr or not tr.get("devices") or not tr["window_s"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])


def mean_decode_context(ctx) -> Optional[float]:
    """Mean context of a row while it decodes (its prompt plus half its
    answer), over the requests that finished in the window."""
    ctxs = []
    for tl in ctx["flight"]:
        prompt = event_attr(tl, "submit", "prompt_tokens")
        gen = event_attr(tl, "engine_finish", "generated")
        if prompt is not None and gen is not None:
            ctxs.append(prompt + gen / 2.0)
    return sum(ctxs) / len(ctxs) if ctxs else None


def decode_roofline_share(ctx, p) -> Optional[float]:
    """The least time the chip could take for one decode step — the
    adapter's ``decode_step_floor_s`` for the rows per decode dispatch
    and the mean context of a decoding row — over the measured device
    time of a step, percent."""
    step_ms = ctx["read"](p["time_metric"])
    rows = span_mean(ctx, {"kind": "decode", "field": "rows"})
    context = mean_decode_context(ctx)
    if not step_ms or rows is None or context is None:
        return None
    floor_s = ctx["adapter"].decode_step_floor_s(ctx["config"], ctx["peaks"], rows, context)
    return 100.0 * floor_s / (step_ms / 1000.0)


READERS: Dict[str, Callable[[Dict[str, Any], Dict[str, Any]], Optional[float]]] = {
    "flight_phase_percentile": flight_phase_percentile,
    "client_other_percentile": client_other_percentile,
    "client_tpot_percentile": client_tpot_percentile,
    "client_gap_percentile": client_gap_percentile,
    "span_mean": span_mean,
    "device_module_ms": device_module_ms,
    "device_op_busy_share": device_op_busy_share,
    "device_idle_share": device_idle_share,
    "decode_roofline_share": decode_roofline_share,
}


def resolve(name: str, roots: Sequence[str]) -> Callable[[Dict[str, Any], Dict[str, Any]], Optional[float]]:
    """The reader a metric file names: a key of ``READERS``, or
    ``<module>:<function>`` of a module whose file lies under one of
    ``roots`` (the manifest's ``paths``: the yardstick stays where a PR
    that claims a gain cannot change it). Anything else raises."""
    if name in READERS:
        return READERS[name]
    module_name, sep, function = name.partition(":")
    if not sep or not function:
        raise ValueError(f"reader {name!r} is neither a key of readers.READERS nor '<module>:<function>'")
    reader = getattr(arch.module_under(module_name, roots), function, None)
    if not callable(reader):
        raise ValueError(f"reader {name!r}: {module_name} has no callable {function!r}")
    return reader


def join_in_order(requests: List[Dict[str, Any]], flight: List[Dict[str, Any]],
                  slack_s: float = 2.0) -> List[Tuple[Dict[str, Any], Dict[str, Any]]]:
    """Pair client requests with server timelines by order of arrival:
    the i-th send with the i-th ``started_at`` (both wall clocks of one
    host), dropping a pair whose clocks disagree by more than ``slack_s``.
    There is no request id on the wire without tracing (PERF.md)."""
    reqs = sorted((r for r in requests if r["status"] == "ok"), key=lambda r: r["send_wall"])
    tls = sorted(flight, key=lambda t: t.get("started_at", 0.0))
    out, j = [], 0
    for r in reqs:
        while j < len(tls) and tls[j].get("started_at", 0.0) < r["send_wall"] - 0.05:
            j += 1
        if j < len(tls) and tls[j]["started_at"] - r["send_wall"] <= slack_s:
            out.append((r, tls[j]))
            j += 1
    return out
