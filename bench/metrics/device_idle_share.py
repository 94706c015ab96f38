"""Device: the share of the traced window in which no operation ran.

One minus the union of the device's operation intervals over the
window, from the profiler's trace.  Moves ``output_tokens_per_s``."""

import trace_reduce


def read(run):
    if run.trace is None or not run.trace["device_ops"]:
        return None
    return 100.0 * (1.0 - trace_reduce.busy_s(run.trace)
                    / trace_reduce.window_s(run.trace))
