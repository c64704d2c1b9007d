"""From a profiler trace to device numbers, in two steps.

``extract`` reads the ``.xplane.pb`` the JAX profiler wrote and keeps
plain events: per TPU device the operations of its ``XLA Ops`` line and
of its ``Async XLA Ops`` line, and the host's events (names, start,
duration, all in ns on one clock).  A device event's name is the HLO
instruction's text, ``%<op name> = <shape> <opcode>(<operands>) ...``;
operations are told apart by the op name, never by their operands.
``reduce`` turns such events into numbers.  It is pure Python, so a
small recorded fixture checks it on the CPU.

Within the traced window (the host span named ``WINDOW_SPAN``):

* busy       union of the intervals in which an operation ran, per device
* idle share 1 - busy / window
* compute    summed duration of the operations that are not copies
* collective summed duration of all-gather / all-reduce / ... operations,
             synchronous or asynchronous (start to done)

Numbers per device are averaged over the devices that ran anything.
Idle gaps longer than ``GAP_MIN_NS`` on the first device are charged to
the host event that overlaps them most, so the breakdown says what the
host was doing while the chip waited.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from typing import Any, Dict, List, Optional, Tuple

#: the benchmark's host span around the traced window
WINDOW_SPAN = "chipbench.window"
COPY_RE = re.compile(r"^copy")
COLLECTIVE_RE = re.compile(
    r"^(all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all)")
_OP_NAME = re.compile(r"^%?([^\s=]+)")
GAP_MIN_NS = 50_000
#: characters of an operation's HLO text that name it in the breakdown
OP_TEXT = 160
HOST_SPAN_MAX_NS = 100_000_000
#: host events that say nothing about what the host was doing
_HOST_SKIP = re.compile(r"^\$|ThreadpoolListener|^" + re.escape(WINDOW_SPAN))


def find_xplane(log_dir: str) -> str:
    hits = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                            recursive=True))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return hits[-1]


def extract(path: str) -> Dict[str, Any]:
    """Plain events of one trace: ``{"devices": {id: [[name, start_ns,
    dur_ns], ...]}, "host": [[name, start_ns, dur_ns], ...]}``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[str, List] = {}
    async_ops: Dict[str, List] = {}
    host: List = []
    for plane in data.planes:
        m = re.match(r"/device:TPU:(\d+)$", plane.name)
        if m:
            for line in plane.lines:
                into = {"XLA Ops": devices,
                        "Async XLA Ops": async_ops}.get(line.name)
                if into is not None:
                    into[m.group(1)] = [[e.name, float(e.start_ns),
                                         float(e.duration_ns)]
                                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend([e.name, float(e.start_ns), float(e.duration_ns)]
                            for e in line.events)
    return {"devices": devices, "async": async_ops, "host": host}


def op_name(event_name: str) -> str:
    """``fusion.4`` of ``%fusion.4 = (f32[32,10]...) fusion(...)``."""
    m = _OP_NAME.match(event_name)
    return m.group(1) if m else event_name


def _window(ev: Dict[str, Any]) -> Optional[Tuple[float, float]]:
    spans = [(s, s + d) for name, s, d in ev["host"] if name == WINDOW_SPAN]
    if spans:
        return min(a for a, _ in spans), max(b for _, b in spans)
    return None


def _clip(s: float, d: float, lo: float, hi: float) -> Tuple[float, float]:
    a, b = max(s, lo), min(s + d, hi)
    return a, max(0.0, b - a)


def _union(intervals: List[Tuple[float, float]]) -> Tuple[float, List]:
    """Total covered length and the merged intervals."""
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return sum(b - a for a, b in merged), merged


def reduce(ev: Dict[str, Any], top: int = 10) -> Optional[Dict[str, Any]]:
    """Device numbers of the traced window; None when the trace holds no
    window span or no device operation inside it."""
    win = _window(ev)
    if win is None:
        return None
    lo, hi = win
    per_dev = []
    op_time: Dict[str, float] = defaultdict(float)
    first_merged: List = []
    for dev in sorted(ev["devices"], key=int):
        busy_iv, compute, coll = [], 0.0, 0.0
        for name, s, d in ev["devices"][dev]:
            a, w = _clip(s, d, lo, hi)
            if w <= 0.0:
                continue
            busy_iv.append((a, a + w))
            op_time[name[:OP_TEXT]] += w
            op = op_name(name)
            if not COPY_RE.match(op):
                compute += w
            if COLLECTIVE_RE.match(op):
                coll += w
        for name, s, d in ev.get("async", {}).get(dev, []):
            if COLLECTIVE_RE.match(op_name(name)):
                coll += _clip(s, d, lo, hi)[1]
        if not busy_iv:
            continue
        busy, merged = _union(busy_iv)
        if not first_merged:
            first_merged = merged
        per_dev.append((busy, compute, coll))
    if not per_dev:
        return None
    n = len(per_dev)
    window_s = (hi - lo) * 1e-9
    busy_s = sum(p[0] for p in per_dev) / n * 1e-9
    ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:top]
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / window_s,
        "compute_s": sum(p[1] for p in per_dev) / n * 1e-9,
        "collective_s": sum(p[2] for p in per_dev) / n * 1e-9,
        "devices": n,
        "device_ops": [[name, t / n * 1e-9] for name, t in ops],
        "idle_gaps": _idle_gaps(first_merged, lo, hi, ev["host"], top),
    }


def _idle_gaps(merged: List, lo: float, hi: float, host: List,
               top: int) -> List[List[Any]]:
    """Idle time of one device, charged per gap to the shortest host
    event that covers at least half of it, else to the one that overlaps
    it most, else to ``idle``.  Host events longer than ``HOST_SPAN_MAX_NS``
    enclose whole phases and say nothing about one gap."""
    gaps, prev = [], lo
    for a, b in merged:
        if a - prev >= GAP_MIN_NS:
            gaps.append((prev, a))
        prev = max(prev, b)
    if hi - prev >= GAP_MIN_NS:
        gaps.append((prev, hi))
    if not gaps:
        return []
    spans = sorted((s, s + d, name) for name, s, d in host
                   if 0 < d <= HOST_SPAN_MAX_NS and not _HOST_SKIP.search(name))
    starts = [s for s, _, _ in spans]
    charged: Dict[str, float] = defaultdict(float)
    for ga, gb in gaps:
        best, best_w, cover, cover_d = "idle", 0.0, None, None
        i = bisect.bisect_left(starts, ga - HOST_SPAN_MAX_NS)
        while i < len(spans) and spans[i][0] < gb:
            s, e, name = spans[i]
            w = min(e, gb) - max(s, ga)
            if w > best_w:
                best, best_w = name, w
            if w >= 0.5 * (gb - ga) and (cover_d is None or e - s < cover_d):
                cover, cover_d = name, e - s
            i += 1
        charged[cover or best] += (gb - ga) * 1e-9
    return [[k, v] for k, v in
            sorted(charged.items(), key=lambda kv: -kv[1])[:top]]
