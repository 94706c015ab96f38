"""The program's own spans, from a traced run's profile.

``trace_reduce.py`` reduces the profile to the benchmark's spans and the
device's operations.  This module reads the same ``.xplane.pb`` for the
host spans the serving engine writes there itself, its
``jax.profiler.TraceAnnotation``s named ``engine.*`` and ``stepper.*``:
``engine.step`` around a tick, and inside it ``engine.schedule``,
``stepper.stage``, ``stepper.wait``, ``stepper.fetch`` and
``engine.emit``.  Device idle time inside them is given to the innermost
one.

Reduced, it is a plain dict, so the metrics can be tested on a
hand-built one::

    {"spans": [(name, start_ns, end_ns), ...]}

It is parsed once per run and kept in the run's reduced trace under
``"program"``.  The window and the device's operations are the
benchmark's (``run.trace``), on the same clock.  A program without these
spans reduces to an empty list, and every reader then returns None.

    python3 bench/program_trace.py [log_dir]

prints the window's device-idle seconds split by innermost program span.
"""

from __future__ import annotations

import glob
import json
import os
import sys
import types
from typing import Dict, Iterable, List, Optional, Tuple

import trace_reduce

PREFIXES = ("engine.", "stepper.")
STEP = "engine.step"
TRACE_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), ".trace")

Span = Tuple[str, int, int]


def load(log_dir: str = TRACE_DIR) -> Optional[dict]:
    """The program's spans in the newest ``.xplane.pb`` under
    ``log_dir``, or None where there is none."""
    import jax
    files = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not files:
        return None
    data = jax.profiler.ProfileData.from_file(files[-1])
    spans = [(ev.name, int(ev.start_ns), int(ev.start_ns + ev.duration_ns))
             for plane in data.planes if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events
             if ev.name.startswith(PREFIXES)]
    return {"spans": sorted(spans, key=lambda x: (x[1], -x[2]))}


def of(run) -> Optional[dict]:
    """The reduced program trace of a traced run, parsed on first use."""
    if run.trace is None:
        return None
    if "program" not in run.trace:
        run.trace["program"] = load() or {"spans": []}
    return run.trace["program"]


def exclusive(spans: Iterable[Span]) -> Dict[str, List[Tuple[int, int]]]:
    """Each span's time not inside a span nested in it, by name: the
    time given to it as the innermost span.  Spans of one thread nest."""
    pieces: Dict[str, List[Tuple[int, int]]] = {}
    stack: List[list] = []            # [name, end, cursor]

    def pop():
        name, end, cursor = stack.pop()
        pieces.setdefault(name, []).append((cursor, end))
        if stack:
            stack[-1][2] = max(stack[-1][2], end)

    for name, s, e in sorted(spans, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][1] <= s:
            pop()
        if stack:
            top = stack[-1]
            pieces.setdefault(top[0], []).append((top[2], s))
            e = min(e, top[1])
        stack.append([name, e, s])
    while stack:
        pop()
    return {k: trace_reduce.merge(v) for k, v in pieces.items()}


def steps(run) -> List[Tuple[int, int]]:
    """The ``engine.step`` spans that lie wholly in the window."""
    prog = of(run)
    if prog is None:
        return []
    lo, hi = run.trace["window"]
    return [(s, e) for name, s, e in prog["spans"]
            if name == STEP and s >= lo and e <= hi]


def idle_by_span(run) -> Dict[str, float]:
    """Device-idle seconds in the window, given to the innermost program
    span the host was in, averaged over the devices."""
    prog = of(run)
    devs = sorted(run.trace["device_ops"]) if run.trace else []
    if prog is None or not devs:
        return {}
    out = {}
    for name, ivs in exclusive(prog["spans"]).items():
        ivs = trace_reduce.clip(ivs, run.trace["window"])
        busy = sum(trace_reduce.overlap(trace_reduce.busy(run.trace, d), ivs)
                   for d in devs) / len(devs)
        out[name] = (trace_reduce.total(ivs) - busy) / 1e9
    return out


def idle_ms_per_step(run, span: str) -> Optional[float]:
    """Device-idle milliseconds inside ``span`` as the innermost program
    span, per ``engine.step`` in the window."""
    n = len(steps(run))
    idle = idle_by_span(run)
    if not n or span not in idle:
        return None
    return 1e3 * idle[span] / n


def main(argv=None) -> int:
    log_dir = (argv or sys.argv[1:] or [TRACE_DIR])[0]
    run = types.SimpleNamespace(trace=trace_reduce.load(log_dir))
    if run.trace is None:
        print(f"no traced window under {log_dir}", file=sys.stderr)
        return 1
    run.trace["program"] = load(log_dir)
    idle = trace_reduce.window_s(run.trace) - trace_reduce.busy_s(run.trace)
    split = idle_by_span(run)
    split["outside every step"] = idle - sum(split.values())
    print(json.dumps({"window_s": trace_reduce.window_s(run.trace),
                      "idle_s": idle, "steps": len(steps(run)),
                      "idle_s_by_innermost_span": split}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
