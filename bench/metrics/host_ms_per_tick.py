"""Stepper, host side: host milliseconds per engine tick.

For every ``Engine.step`` span wholly in the traced window, the span's
time not covered by device work, summed and divided by the number of
ticks.  Moves ``itl_p95_s``."""

import trace_reduce


def read(run):
    if run.trace is None or not run.trace["device_ops"]:
        return None
    steps = trace_reduce.inside(run.trace, "bench.step")
    if not steps:
        return None
    span = sum(e - s for s, e in steps) / 1e9
    device = trace_reduce.device_time_in(run.trace, steps)
    return 1e3 * (span - device) / len(steps)
