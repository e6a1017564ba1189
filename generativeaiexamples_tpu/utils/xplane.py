"""Shared xplane/Chrome-trace parsing for jax.profiler captures.

Extracted from ``tools/profile_decode.py`` (which predates the paged /
spec / scheduler engine paths) so every consumer of a
``jax.profiler.trace`` capture reads the device track the same way:

- the decode profiler (``tools/profile_decode.py``) attributes device
  time across Pallas kernels, fusions, cache scatters, copies,
  sampling and collectives;
- the dispatch timeline (``engine/dispatch_timeline.py`` /
  ``GET /internal/timeline?format=perfetto&xplane=<logdir>``) replaces
  its host-return device-time *estimates* with measured on-chip spans
  — a host clock sees enqueue and readback, not execution; the xplane
  device track is ground truth.

Pure host parsing: no jax import, just the trace.json.gz files the
profiler plugin writes under ``<logdir>/plugins/profile/<run>/``.
"""
from __future__ import annotations

import collections
import glob
import gzip
import json
import os
from typing import Any, Dict, List

__all__ = [
    "categorize",
    "find_trace_file",
    "load_trace_events",
    "parse_trace",
    "device_track_events",
]


def categorize(name: str) -> str:
    """Bucket one HLO-op span name into the decode-step categories the
    profiler report groups by."""
    n = name.lower()
    if "custom-call" in n or "tpu_custom_call" in n or "pallas" in n:
        return "pallas-kernel"
    if "dynamic-update-slice" in n or "scatter" in n:
        return "cache-scatter"
    if n.startswith("copy") or "transpose" in n or "bitcast" in n:
        return "copy/layout"
    if "sort" in n or "top-k" in n or "rng" in n or "iota" in n:
        return "sampling"
    if "all-reduce" in n or "all-gather" in n or "collective" in n:
        return "collective"
    if "fusion" in n or "dot" in n or "convolution" in n:
        return "fusion/matmul"
    return "other"


def find_trace_file(logdir: str) -> str:
    """The newest trace.json.gz under a capture directory (raises
    FileNotFoundError when the profiler wrote nothing)."""
    files = glob.glob(
        os.path.join(logdir, "plugins/profile/*/*.trace.json.gz")
    )
    if not files:
        raise FileNotFoundError(f"no trace under {logdir}")
    return sorted(files)[-1]


def load_trace_events(logdir: str) -> List[Dict[str, Any]]:
    """Raw Chrome-trace events from the newest capture under logdir."""
    with gzip.open(find_trace_file(logdir)) as fh:
        data = json.load(fh)
    return data["traceEvents"]


def _device_pids(events: List[Dict[str, Any]]) -> set:
    pids = {
        e["pid"]: e["args"].get("name", "")
        for e in events
        if e.get("ph") == "M" and e.get("name") == "process_name"
    }
    return {p for p, n in pids.items() if "TPU" in n}


def parse_trace(logdir: str) -> Dict[str, Any]:
    """Device-time attribution over one capture: executable-level spans
    (``jit_<name>``) vs HLO-op spans, op category sums, and the traced
    device wall. The report shape is pinned by
    ``tools/profile_decode.py``'s stdout contract."""
    evs = load_trace_events(logdir)
    tpu_pids = _device_pids(evs)
    # Two kinds of device events: executable-level spans (jit_<name>) and
    # HLO-op-level spans. Separate by name.
    exe = collections.defaultdict(float)
    exe_n = collections.Counter()
    ops = collections.defaultdict(float)
    ops_n = collections.Counter()
    cats = collections.defaultdict(float)
    tmin, tmax = float("inf"), 0.0
    for e in evs:
        if e.get("ph") != "X" or e.get("pid") not in tpu_pids:
            continue
        name = e.get("name", "")
        dur = float(e.get("dur", 0.0))  # us
        ts = float(e.get("ts", 0.0))
        tmin, tmax = min(tmin, ts), max(tmax, ts + dur)
        if name.startswith("jit_") or name.startswith("jit__"):
            base = name.split("(")[0]
            exe[base] += dur
            exe_n[base] += 1
        else:
            ops[name] += dur
            ops_n[name] += 1
            cats[categorize(name)] += dur
    wall = tmax - tmin if tmax > tmin else 0.0
    return {
        "wall_us": wall,
        "executables": dict(exe),
        "exe_counts": dict(exe_n),
        "ops": dict(ops),
        "op_counts": dict(ops_n),
        "categories": dict(cats),
    }


def device_track_events(logdir: str) -> List[Dict[str, Any]]:
    """Executable-level device spans as flat dicts for the dispatch
    timeline's Perfetto device track: ``{"name", "ts_us", "dur_us",
    "tid"}``, chronological. Only ``jit_*`` executable spans — op-level
    spans belong to the deep-dive profiler report, not the serving
    timeline."""
    evs = load_trace_events(logdir)
    tpu_pids = _device_pids(evs)
    out: List[Dict[str, Any]] = []
    for e in evs:
        if e.get("ph") != "X" or e.get("pid") not in tpu_pids:
            continue
        name = e.get("name", "")
        if not (name.startswith("jit_") or name.startswith("jit__")):
            continue
        out.append(
            {
                "name": name.split("(")[0],
                "ts_us": float(e.get("ts", 0.0)),
                "dur_us": float(e.get("dur", 0.0)),
                "tid": int(e.get("tid", 1)),
            }
        )
    out.sort(key=lambda d: d["ts_us"])
    return out
