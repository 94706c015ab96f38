"""Stepper, host side: device-idle milliseconds per tick in staging.

Device-idle time inside the stepper's ``stepper.stage`` spans (the
pool's record of the new rows, copy-on-write page copies, block tables,
the uploads and the step call until it returns), given to the innermost
program span and clipped to the window, over the ``engine.step`` spans
in the window.  Moves ``itl_p95_s``."""

import program_trace


def read(run):
    return program_trace.idle_ms_per_step(run, "stepper.stage")
