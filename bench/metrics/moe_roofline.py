"""Kernels against the chip: the expert ops' share of their roofline.

For each decode call whose span lies wholly in the traced window: the
weights of the (layer, expert) pairs it hit (``hit`` of its
``moe.counts`` marker, gate, up and down of width ``d x f`` each, at
float32) plus the routed rows in and out (``routed`` rows of ``d``
floats each way), over the HBM bandwidth, against the device time of
the expert ops in the call's span.  The expert matmuls of one decode
step are bound by reading the experts' weights; a form that reads every
expert's (a decode step's batched matmuls do) tops out at the hit share
of 100%.  Moves ``output_tokens_per_s``."""

import moe_trace

F32 = 4


def read(run):
    calls = moe_trace.decode_calls(run)
    if not calls:
        return None
    s = run.cell.family.sizes(run.cell.cfg)
    expert = 3 * s["d"] * s["f"] * F32
    need = sum(hit * expert + routed * 2 * s["d"] * F32
               for _, hit, routed in calls)
    device = moe_trace.expert_s(run.trace, [span for span, _, _ in calls])
    if device <= 0:
        return None
    return 100.0 * need / float(run.peaks["hbm_bytes_per_s"]) / device
