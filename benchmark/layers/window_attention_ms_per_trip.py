"""window_attention_ms_per_trip (layer: kernels) - device time of the window
layers' attention in the decode step (the decode kernel over each window
layer's ring under min(length + 1, sliding_window) rows, the queries laid
out for it and its result folded: the operations under the program's
`window_attention` scope, all window layers) per decode TRIP, over the
dispatches that lie inside the profiled sub-window, in ms.  A dispatch's
`trips` ride its `serving/decode_step` span.  How the operations are found:
benchmark/moe_trace.py; a program with no such scope gives no reading."""

from benchmark import ssm_trace


def read(spans, trace, run):
    return ssm_trace.scope_ms_per_trip(spans, trace, run, "window_attention")
