"""Model step: the share of (layer, expert) pairs a decode call hits.

The median, over the decode calls whose span lies wholly in the traced
window, of ``hit`` from the call's ``moe.counts`` marker (the pairs that
got at least one live row) over layers times experts.  Explains the
bytes of the expert ops: at 16 live rows, each routed to 8 of 64
experts, uniform routing hits 64 (1 - (7/8)^16) = 56.4 of 64.  Moves
``output_tokens_per_s``."""

import statistics

import moe_trace


def read(run):
    calls = moe_trace.decode_calls(run)
    if not calls:
        return None
    s = run.cell.family.sizes(run.cell.cfg)
    return 100.0 * statistics.median(
        hit / (s["L"] * s["E"]) for _, hit, _ in calls)
