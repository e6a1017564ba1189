"""From a profiler trace to device numbers: the benchmark's own reduction.

The device track of a ``jax.profiler`` capture is the only sound source
of device time (a host clock sees enqueue and readback). This module
reads the ``.xplane.pb`` the profiler wrote (``jax.profiler.ProfileData``
— nothing but jax is needed) into a flat event list and reduces it:

- **busy**: the union of the intervals in which an operation ran on a
  device (``XLA Ops`` line); ``idle share = 1 - busy / window``;
- **operation self time** by base name (``fusion.12`` -> ``fusion``):
  an operation's duration minus the part its children on the same line
  cover (a ``while`` spans the body it runs), so shares add up to busy;
- **module time** by program (``XLA Modules`` line, ``jit_<name>``);
- **idle gaps** between modules, labelled by the programs around them
  (the host's own spans are not on the profiler's clock yet — PERF.md
  lists that for the tracing issue), and the sum of the short gaps
  between operations inside a program.

It is checked on a small recorded event list kept in
``perfbench/fixtures``. Run as a script it prints one JSON object: the
parent process of the benchmark never imports jax, so it calls this in a
child after the server has exited.
"""
from __future__ import annotations

import glob
import json
import os
import re
import sys
from typing import Any, Dict, Iterable, List, Tuple

Event = Tuple[str, str, str, int, int]  # plane, line, name, start_ns, dur_ns

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SHORT_GAP_NS = 20_000


def find_xplane(logdir: str) -> str:
    files = sorted(glob.glob(os.path.join(logdir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return files[-1]


def read_xplane(path: str) -> List[Event]:
    """Device-plane events of one capture, flat."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out: List[Event] = []
    for plane in data.planes:
        if not is_device_plane(plane.name):
            continue
        for line in plane.lines:
            if line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                out.append((plane.name, line.name, ev.name, int(ev.start_ns), int(ev.duration_ns)))
    return out


def is_device_plane(name: str) -> bool:
    return name.startswith("/device:TPU:") and "host" not in name.lower()


def base_name(name: str) -> str:
    """``%fusion.123 = bf16[64,4096]{1,0} fusion(...)`` -> ``fusion``;
    ``jit_decode_paged(123...)`` -> ``jit_decode_paged``."""
    name = name.split(" = ")[0].lstrip("%").split("(")[0].strip()
    return re.sub(r"(\.\d+)+$", "", name) or name


def union_ns(intervals: Iterable[Tuple[int, int]]) -> int:
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def self_times_ns(events: List[Tuple[str, int, int]]) -> Dict[str, int]:
    """Exclusive time by base name for (name, start, dur) events of ONE
    line, where an event may contain later, shorter ones."""
    out: Dict[str, int] = {}
    stack: List[List[Any]] = []  # [name, end, self]

    def close_until(t: int) -> None:
        while stack and stack[-1][1] <= t:
            name, _, self_ns = stack.pop()
            out[name] = out.get(name, 0) + max(0, self_ns)

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        close_until(start)
        if stack:
            stack[-1][2] -= min(dur, stack[-1][1] - start)
        stack.append([base_name(name), start + dur, dur])
    close_until(1 << 62)
    return out


def reduce_events(events: List[Event]) -> Dict[str, Any]:
    """The summary every device reader works from."""
    planes = sorted({e[0] for e in events if is_device_plane(e[0])})
    if not planes:
        return {"devices": 0}
    busy, t_min, t_max = [], None, None
    ops_self: Dict[str, int] = {}
    modules: Dict[str, Dict[str, float]] = {}
    gaps: Dict[str, int] = {}
    short_gap_ns = 0
    for plane in planes:
        ops = [(n, s, d) for p, l, n, s, d in events if p == plane and l == OPS_LINE]
        mods = sorted((s, d, n) for p, l, n, s, d in events if p == plane and l == MODULES_LINE)
        spans = [(s, s + d) for _, s, d in ops] or [(s, s + d) for s, d, _ in mods]
        if not spans:
            continue
        busy.append(union_ns(spans))
        lo, hi = min(s for s, _ in spans), max(e for _, e in spans)
        t_min = lo if t_min is None else min(t_min, lo)
        t_max = hi if t_max is None else max(t_max, hi)
        for name, ns in self_times_ns(ops).items():
            ops_self[name] = ops_self.get(name, 0) + ns
        for s, d, n in mods:
            m = modules.setdefault(base_name(n), {"count": 0, "total_ns": 0})
            m["count"] += 1
            m["total_ns"] += d
        for (s0, d0, n0), (s1, _, n1) in zip(mods, mods[1:]):
            gap = s1 - (s0 + d0)
            if gap > SHORT_GAP_NS:
                key = f"after_{base_name(n0)}_before_{base_name(n1)}"
                gaps[key] = gaps.get(key, 0) + gap
        merged = sorted(spans)
        end = merged[0][1]
        for s, e in merged[1:]:
            if 0 < s - end <= SHORT_GAP_NS:
                short_gap_ns += s - end
            end = max(end, e)
    n = len(busy)
    if not n:
        return {"devices": 0}
    if short_gap_ns:
        gaps["between_ops_lt_20us"] = short_gap_ns
    return {
        "devices": n,
        "window_s": (t_max - t_min) / 1e9,
        "busy_s": sum(busy) / n / 1e9,
        "ops_self_s": {k: v / n / 1e9 for k, v in ops_self.items()},
        "modules": {
            k: {"count": v["count"] / n, "total_s": v["total_ns"] / n / 1e9}
            for k, v in modules.items()
        },
        "idle_gaps_s": {k: v / n / 1e9 for k, v in gaps.items()},
    }


def top(d: Dict[str, float], k: int = 10) -> List[List[Any]]:
    return [[name, value] for name, value in sorted(d.items(), key=lambda kv: -kv[1])[:k]]


def breakdown(summary: Dict[str, Any]) -> Dict[str, Any]:
    return {
        "device_ops": top(summary.get("ops_self_s", {})),
        "idle_gaps": top(summary.get("idle_gaps_s", {})),
    }


def matching_s(table: Dict[str, float], pattern: str) -> float:
    rx = re.compile(pattern)
    return sum(v for k, v in table.items() if rx.search(k))


def main(argv: List[str]) -> int:
    logdir = argv[1]
    events = read_xplane(find_xplane(logdir))
    summary = reduce_events(events)
    if len(argv) > 2:  # keep a small sample of the raw events for the fixtures
        with open(argv[2], "w", encoding="utf-8") as fh:
            json.dump(events[: int(argv[3]) if len(argv) > 3 else 4000], fh)
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
