"""Mixture-of-experts readings of a traced run: the program's
``moe.counts`` markers and the routed-expert ops on the device.

After each step call the stepper of a mixture-of-experts model writes a
zero-length host event ``moe.counts`` into the profiler's trace, with two
stats: ``hit``, the (layer, expert) pairs that got at least one live row
in the call, and ``routed``, the (row, expert) assignments.  The expert
ops are the device ops that read a layer's expert weights: a TPU op
event is named by its HLO text, which names each operand, and the
weights are the program's values ``l{i}.we_gate``, ``we_up`` and
``we_down`` (``params__l0_we_gate__`` there), whether the op is one of
the grouped matmuls (Mosaic kernels named ``ragged-dot``) or a fusion
of a decode step's batched expert matmuls.

Reduced, the markers are a plain dict, so the metrics can be tested on a
hand-built one::

    {"markers": [(t_ns, hit, routed), ...]}

It is parsed once per run and kept in the run's reduced trace under
``"moe"``.  The window, the spans and the device's operations are the
benchmark's (``run.trace``), on the same clock.  A program without the
markers reduces to an empty list, and every reader then returns None.
"""

from __future__ import annotations

import glob
import os
from typing import List, Optional, Sequence, Tuple

import trace_reduce

MARKER = "moe.counts"
# what names an expert op in its event's HLO text
EXPERT_OPS = ("we_gate", "we_up", "we_down", "ragged-dot")
TRACE_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), ".trace")


def load(log_dir: str = TRACE_DIR) -> Optional[dict]:
    """The ``moe.counts`` markers in the newest ``.xplane.pb`` under
    ``log_dir``, or None where there is none."""
    import jax
    files = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not files:
        return None
    data = jax.profiler.ProfileData.from_file(files[-1])
    markers = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == MARKER:
                    stats = dict(ev.stats)
                    markers.append((int(ev.start_ns), int(stats["hit"]),
                                    int(stats["routed"])))
    return {"markers": sorted(markers)}


def of(run) -> Optional[dict]:
    """The reduced markers of a traced run, parsed on first use."""
    if run.trace is None:
        return None
    if "moe" not in run.trace:
        run.trace["moe"] = load() or {"markers": []}
    return run.trace["moe"]


def is_expert_op(name: str) -> bool:
    return any(k in name for k in EXPERT_OPS)


def expert_s(trace: dict, intervals: Sequence[Tuple[int, int]]) -> float:
    """Device seconds of expert ops inside ``intervals`` (clipped to the
    window), averaged over the devices."""
    devs = sorted(trace["device_ops"])
    if not devs:
        return 0.0
    ivs = trace_reduce.merge(intervals)
    total = 0
    for d in devs:
        ops = trace_reduce.clip(
            trace_reduce.merge((s, e) for name, s, e in trace["device_ops"][d]
                               if is_expert_op(name)), trace["window"])
        total += trace_reduce.overlap(ops, ivs)
    return total / len(devs) / 1e9


def decode_calls(run) -> List[Tuple[Tuple[int, int], int, int]]:
    """``(span, hit, routed)`` for each decode call whose span lies
    wholly in the traced window and holds a ``moe.counts`` marker."""
    moe = of(run)
    if not moe or not moe["markers"]:
        return []
    out = []
    for _, (s, e) in run.traced_calls("decode"):
        inside = [(hit, routed) for t, hit, routed in moe["markers"]
                  if s <= t <= e]
        if inside:
            out.append(((s, e), *inside[-1]))
    return out
