"""Model step: milliseconds of one decode call, host clock.

The median of the host time around ``stepper.decode``, which blocks
until the logits reach the host, over the calls made wholly in the
window.  Moves ``itl_p95_s``."""

import statistics


def read(run):
    calls = run.calls("decode")
    if not calls:
        return None
    return 1e3 * statistics.median(c.t1 - c.t0 for c in calls)
