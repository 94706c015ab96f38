"""Engine scheduler: the share of prefill rows that carry a prompt token.

Useful rows (the prompt tokens of the live slots) over the rows every
prefill call computes (``n_slots x chunk``), for the calls made wholly in
the window, from the benchmark's wrapper on ``stepper.prefill``.  Moves
``ttft_p90_s``."""

import numpy as np


def read(run):
    calls = run.calls("prefill")
    if not calls:
        return None
    sv = run.cell.serving
    useful = sum(int(np.sum(c.n_new)) for c in calls)
    return 100.0 * useful / (len(calls) * int(sv["n_slots"]) * int(sv["chunk"]))
