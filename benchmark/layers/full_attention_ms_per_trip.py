"""full_attention_ms_per_trip (layer: kernels) - device time of the full
layers' attention in the decode step of a stack that has window layers too
(the decode kernel over each full layer's table under length + 1 rows, the
queries laid out for it and its result folded: the operations under the
program's `full_attention` scope, all full layers) per decode TRIP, over the
dispatches that lie inside the profiled sub-window, in ms: what a stream's
whole length costs beside `window_attention_ms_per_trip`, which no length
moves.  How the operations are found: benchmark/moe_trace.py; a program with
no such scope gives no reading."""

from benchmark import ssm_trace


def read(spans, trace, run):
    return ssm_trace.scope_ms_per_trip(spans, trace, run, "full_attention")
