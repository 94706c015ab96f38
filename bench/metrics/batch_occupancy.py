"""Engine scheduler: the share of slots that decode a token in a decode call.

Live slots per decode call over ``n_slots``, for the calls made wholly in
the window, from the benchmark's wrapper on ``stepper.decode``.  Moves
``output_tokens_per_s``."""


def read(run):
    calls = run.calls("decode")
    if not calls:
        return None
    n = int(run.cell.serving["n_slots"])
    return 100.0 * sum(len(c.n_new) for c in calls) / (len(calls) * n)
