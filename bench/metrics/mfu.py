"""Device: the model's operations in the window over the chip's peak.

Operations of every useful row the engine computed in the calls made
wholly in the window (prompt rows in prefill calls, one row per live
slot in decode calls, each with its causal attention) over the window's
seconds times the chip's matrix peak.  Moves ``output_tokens_per_s``."""

import numpy as np


def read(run):
    fam, cfg = run.cell.family, run.cell.cfg
    flops = 0.0
    for call in run.calls("prefill") + run.calls("decode"):
        for start, n in zip(call.rows, call.n_new):
            flops += fam.row_flops(cfg, np.arange(start + 1, start + n + 1))
    if flops == 0:
        return None
    seconds = run.window.t1 - run.window.t0
    return 100.0 * flops / (seconds * float(run.peaks["matmul_flops_per_s"]))
