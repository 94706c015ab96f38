"""Engine scheduler: device-idle milliseconds per tick in scheduling.

Device-idle time inside the engine's ``engine.schedule`` spans (expiry,
overload control, admission with the pool's prefix lookup and
reservation, resume), given to the innermost program span and clipped
to the window, over the ``engine.step`` spans in the window.  Moves
``itl_p95_s``."""

import program_trace


def read(run):
    return program_trace.idle_ms_per_step(run, "engine.schedule")
