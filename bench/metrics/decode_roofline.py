"""Kernels against the chip: the decode step's share of its roofline.

The bytes a decode step must move at float32 (every weight once, each
live slot's cached K/V rows and its new row, its embedding row and its
logits row) over the HBM bandwidth, against the device time in the
decode spans, for the calls whose span lies wholly in the traced window.
A decode step at 16 slots is bound by bytes: its operations at the
matrix peak take a small fraction of that time.  Moves
``output_tokens_per_s``."""

import trace_reduce


def read(run):
    pairs = run.traced_calls("decode")
    if not pairs:
        return None
    fam, cfg = run.cell.family, run.cell.cfg
    need = sum(fam.decode_step_bytes(cfg, call.rows) for call, _ in pairs)
    device = trace_reduce.device_time_in(run.trace, [s for _, s in pairs])
    if device <= 0:
        return None
    return 100.0 * need / float(run.peaks["hbm_bytes_per_s"]) / device
