"""sparse_attention_ms_per_trip (layer: kernels) - device time of the sparse
layers' STAGE 2 in the step (the Mosaic call `sparse_decode_attention` over
the selected blocks of every running slot and what prepares its operands:
the operations under the program's `sparse_attention` scope, all sparse
layers) per decode TRIP, over the dispatches that lie inside the profiled
sub-window, in ms.  A dispatch's `trips` ride its `serving/decode_step` span.
How the operations are found: benchmark/moe_trace.py; a program with no such
scope gives no reading."""

from benchmark import ssm_trace


def read(spans, trace, run):
    return ssm_trace.scope_ms_per_trip(spans, trace, run, "sparse_attention")
