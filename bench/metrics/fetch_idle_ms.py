"""Stepper, host side: device-idle milliseconds per tick in the logits copy.

Device-idle time inside the stepper's ``stepper.fetch`` spans (the
step's logits copied to the host after the device is done), given to
the innermost program span and clipped to the window, over the
``engine.step`` spans in the window.  Moves ``output_tokens_per_s``."""

import program_trace


def read(run):
    return program_trace.idle_ms_per_step(run, "stepper.fetch")
