"""Model step: milliseconds of one prefill call, host clock.

The median of the host time around ``stepper.prefill``, which blocks
until the logits reach the host, over the calls made wholly in the
window.  Moves ``ttft_p90_s``."""

import statistics


def read(run):
    calls = run.calls("prefill")
    if not calls:
        return None
    return 1e3 * statistics.median(c.t1 - c.t0 for c in calls)
