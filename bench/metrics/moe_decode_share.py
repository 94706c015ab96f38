"""Model step: the routed experts' share of the decode step's device time.

Device time of the expert ops (those that read a layer's expert
weights, ``moe_trace.is_expert_op``) inside the decode spans, over all
device time inside those spans, for the calls whose span lies wholly in
the traced window and holds a ``moe.counts`` marker.  Shows that the
experts do the work of the mixture-of-experts cell.  Moves
``output_tokens_per_s``."""

import moe_trace
import trace_reduce


def read(run):
    spans = [span for span, _, _ in moe_trace.decode_calls(run)]
    if not spans:
        return None
    device = trace_reduce.device_time_in(run.trace, spans)
    if device <= 0:
        return None
    return 100.0 * moe_trace.expert_s(run.trace, spans) / device
