"""Device: idle milliseconds per tick while the host waits on the chip.

Device-idle time inside the stepper's ``stepper.wait`` spans (the host
blocks until the step's logits are ready), given to the innermost
program span and clipped to the window, over the ``engine.step`` spans
in the window.  Idle there is work off the XLA Ops line or gaps between
launches.  Moves ``output_tokens_per_s``."""

import program_trace


def read(run):
    return program_trace.idle_ms_per_step(run, "stepper.wait")
