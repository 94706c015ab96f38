"""Engine scheduler: device-idle milliseconds per tick in emission.

Device-idle time inside the engine's ``engine.emit`` spans (the host
argmax over the logits rows, token callbacks, finishing requests and
releasing their pages), given to the innermost program span and clipped
to the window, over the ``engine.step`` spans in the window.  Moves
``itl_p95_s``."""

import program_trace


def read(run):
    return program_trace.idle_ms_per_step(run, "engine.emit")
