"""Reduction of a profiler trace to the numbers the benchmark reports.

A reduced trace is a plain dict, so the reduction can be tested on a
hand-built one::

    {"window": (start_ns, end_ns),           # the "bench.window" span
     "device_ops": {device: [(name, start_ns, end_ns), ...]},
     "spans": {"bench.step": [(start_ns, end_ns), ...], ...}}

Host spans are the benchmark's own ``jax.profiler.TraceAnnotation``s, on
the profiler's clock, so they line up with the device's operations.
Every time here is clipped to the window.  Device time is given to a host
span by where it falls: the serving step blocks until its logits reach
the host, so the device work a call launches ends inside its span.
"""

from __future__ import annotations

import glob
import os
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[int, int]

SPAN_PREFIX = "bench."
WINDOW = "bench.window"
# what the host was doing in an idle gap, innermost first
GAP_LABELS = ("bench.prefill", "bench.decode", "bench.step")
NO_STEP = "no_step"
# an XLA op is named by its whole HLO line; keep its name and shapes
NAME_CHARS = 120


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    """Union of intervals, sorted and disjoint."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Iterable[Interval], window: Interval) -> List[Interval]:
    lo, hi = window
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def total(intervals: Iterable[Interval]) -> int:
    return sum(e - s for s, e in intervals)


def overlap(a: Sequence[Interval], b: Sequence[Interval]) -> int:
    """Length of the intersection of two disjoint sorted interval lists."""
    i = j = n = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            n += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return n


def busy(trace: dict, device: str) -> List[Interval]:
    """Intervals in which an operation ran on ``device``, in the window."""
    ops = trace["device_ops"].get(device, [])
    return clip(merge((s, e) for _, s, e in ops), trace["window"])


def window_s(trace: dict) -> float:
    lo, hi = trace["window"]
    return (hi - lo) / 1e9


def busy_s(trace: dict) -> float:
    """Device-busy seconds in the window, averaged over the devices."""
    devs = sorted(trace["device_ops"])
    if not devs:
        return 0.0
    return sum(total(busy(trace, d)) for d in devs) / len(devs) / 1e9


def inside(trace: dict, span: str) -> List[Interval]:
    """The host spans named ``span`` that lie wholly in the window."""
    lo, hi = trace["window"]
    return sorted((s, e) for s, e in trace["spans"].get(span, [])
                  if s >= lo and e <= hi)


def device_time_in(trace: dict, intervals: Sequence[Interval]) -> float:
    """Device-busy seconds inside ``intervals``, averaged over devices."""
    devs = sorted(trace["device_ops"])
    if not devs:
        return 0.0
    ivs = merge(intervals)
    return sum(overlap(busy(trace, d), ivs) for d in devs) / len(devs) / 1e9


def idle_gaps(trace: dict, limit: int = 10) -> List[Tuple[str, float]]:
    """The longest gaps in which no device ran an operation, each labelled
    by the innermost benchmark span the host was in at its middle, or
    ``no_step`` outside every step (waiting for requests)."""
    lo, hi = trace["window"]
    devs = sorted(trace["device_ops"])
    if not devs:
        return []
    union = merge(iv for d in devs for iv in busy(trace, d))
    gaps, t = [], lo
    for s, e in union + [(hi, hi)]:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    spans = {k: merge(trace["spans"].get(k, [])) for k in GAP_LABELS}

    def label(mid: float) -> str:
        for k in GAP_LABELS:
            if any(s <= mid < e for s, e in spans[k]):
                return k
        return NO_STEP

    gaps.sort(key=lambda g: g[0] - g[1])
    return [(label((s + e) / 2), (e - s) / 1e9) for s, e in gaps[:limit]]


def top_ops(trace: dict, limit: int = 10) -> List[Tuple[str, float]]:
    """Operations by their device seconds in the window, summed by name
    over the devices and averaged over them."""
    devs = sorted(trace["device_ops"])
    sums: Dict[str, int] = {}
    for d in devs:
        for name, s, e in trace["device_ops"][d]:
            for cs, ce in clip([(s, e)], trace["window"]):
                sums[name] = sums.get(name, 0) + ce - cs
    ranked = sorted(sums.items(), key=lambda kv: -kv[1])[:limit]
    return [(name[:NAME_CHARS], ns / len(devs) / 1e9) for name, ns in ranked]


# --------------------------------------------------------------------------- #
# reading the profiler's file
# --------------------------------------------------------------------------- #

def _is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and "CPU" not in name


def _op_line(plane):
    lines = list(plane.lines)
    for ln in lines:
        if ln.name == "XLA Ops":
            return ln
    return None


def load(log_dir: str) -> Optional[dict]:
    """The reduced trace of the newest ``.xplane.pb`` under ``log_dir``,
    or None where there is none or it holds no window span."""
    import jax
    files = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not files:
        return None
    data = jax.profiler.ProfileData.from_file(files[-1])
    ops: Dict[str, List[Tuple[str, int, int]]] = {}
    spans: Dict[str, List[Interval]] = {}
    for plane in data.planes:
        if _is_device_plane(plane.name):
            line = _op_line(plane)
            if line is None:
                continue
            ops[plane.name] = [(ev.name, int(ev.start_ns),
                                int(ev.start_ns + ev.duration_ns))
                               for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.setdefault(ev.name, []).append(
                            (int(ev.start_ns),
                             int(ev.start_ns + ev.duration_ns)))
    if not spans.get(WINDOW):
        return None
    return {"window": spans[WINDOW][0], "device_ops": ops, "spans": spans}
