"""ssm_proj_ms_per_trip (layer: kernels) - device time of the state-space
mixers' projections (ssm_in over the normed input with its segment
multipliers, the gate, the grouped norm, ssm_out: the operations under the
program's `ssm_proj` scope, all layers) per decode TRIP, over the dispatches
that lie inside the profiled sub-window, in ms.  A dispatch's `trips` ride
its `serving/decode_step` span.  How the operations are found:
benchmark/moe_trace.py; a program with no such scope gives no reading."""

from benchmark import ssm_trace


def read(spans, trace, run):
    return ssm_trace.scope_ms_per_trip(spans, trace, run, "ssm_proj")
