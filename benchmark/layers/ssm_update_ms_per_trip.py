"""ssm_update_ms_per_trip (layer: kernels) - device time of the state-space
mixers' recurrence step (the conv over the slot's window and its roll, the
decay of every live slot's scanned state, the outer product added to it, the
read-out: the operations under the program's `ssm_update` scope, all layers)
per decode TRIP, over the dispatches that lie inside the profiled sub-window,
in ms.  A dispatch's `trips` ride its `serving/decode_step` span.  How the
operations are found: benchmark/moe_trace.py; a program with no such scope
gives no reading."""

from benchmark import ssm_trace


def read(spans, trace, run):
    return ssm_trace.scope_ms_per_trip(spans, trace, run, "ssm_update")
