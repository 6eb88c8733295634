"""sparse_select_ms_per_trip (layer: kernels) - device time of the sparse
layers' STAGE 1 (the compressed key a step completes and its landing, the
query heads' scores against the slot's compressed keys, the softmax, the sum
over a K/V head's group, the blocks' max, the forced blocks and the top-k:
the operations under the program's `sparse_select` scope, all sparse layers)
per decode TRIP, over the dispatches that lie inside the profiled sub-window,
in ms.  A dispatch's `trips` ride its `serving/decode_step` span.  How the
operations are found: benchmark/moe_trace.py; a program with no such scope
gives no reading."""

from benchmark import ssm_trace


def read(spans, trace, run):
    return ssm_trace.scope_ms_per_trip(spans, trace, run, "sparse_select")
