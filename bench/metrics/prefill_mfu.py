"""Kernels against the chip: the prefill step's share of the chip's peak.

Operations of the useful prefill rows (each prompt row's matrix products
plus its causal attention over its own context) over the device time in
the prefill spans times the chip's matrix peak, for the calls whose
span lies wholly in the traced window.  Padding rows do not count, so
the padded batch shows as a low share.  Moves ``ttft_p90_s``."""

import numpy as np

import trace_reduce


def read(run):
    pairs = run.traced_calls("prefill")
    if not pairs:
        return None
    fam, cfg = run.cell.family, run.cell.cfg
    flops = 0.0
    for call, _ in pairs:
        for start, n in zip(call.rows, call.n_new):
            flops += fam.row_flops(cfg, np.arange(start + 1, start + n + 1))
    device = trace_reduce.device_time_in(run.trace, [s for _, s in pairs])
    if device <= 0:
        return None
    return 100.0 * flops / (device * float(run.peaks["matmul_flops_per_s"]))
