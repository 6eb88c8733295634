"""attention_share_of_trip (layer: kernels) - the share of a decode trip's
device time that the attending layers' attention takes, in percent: device
seconds of the step's operations under the program's `window_attention` and
`full_attention` scopes (the decode kernel over each kind of table, the
queries laid out for it and its result folded) over the device's busy
seconds, both inside the decode dispatches (`serving/decode_step` spans)
that lie within the profiled sub-window.  It says whether the mechanism
LEADS the cell: beside it stand the routed FFN (`moe_ffn_ms_per_round`) and
the dense projections.  How the operations are found: benchmark/
moe_trace.py; a program with neither scope, or a run with no dispatch in the
sub-window, gives no reading."""

from benchmark import moe_trace


def read(spans, trace, run):
    rounds = moe_trace.rounds_in_profile(spans, run)
    parts = [moe_trace.scope_seconds(trace, run, rounds, scope)
             for scope in ("window_attention", "full_attention")]
    if any(p is None for p in parts):
        return None
    busy = sum(trace.busy_mean(trace.from_monotonic(s["t0"]),
                               trace.from_monotonic(s["t1"]))
               for s in rounds)
    return 100.0 * sum(parts) / busy if busy > 0.0 else None
