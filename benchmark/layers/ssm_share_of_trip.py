"""ssm_share_of_trip (layer: kernels) - the share of a decode trip's device
time that the state-space mixers take, in percent: device seconds of the
step's operations under the program's `ssm_update` scope (the conv's roll and
the recurrence over every running slot's scanned state) and `ssm_proj` scope
(the mixer's two projections, its gate and its norm) over the device's busy
seconds, both inside the decode dispatches (`serving/decode_step` spans)
that lie within the profiled sub-window.  It says whether the mechanism
LEADS the cell: beside it stand the routed or the dense FFN and the
attention (`attention_share_of_trip` does the same for two kinds of K/V
table).  How the operations are found: benchmark/moe_trace.py, from the
configuration's `trace_scopes`; a program with neither scope (a stack with
no state-space mixer), or a run with no dispatch in the sub-window, gives no
reading."""

from benchmark import moe_trace


def read(spans, trace, run):
    rounds = moe_trace.rounds_in_profile(spans, run)
    parts = [moe_trace.scope_seconds(trace, run, rounds, scope)
             for scope in ("ssm_update", "ssm_proj")]
    if any(p is None for p in parts):
        return None
    busy = sum(trace.busy_mean(trace.from_monotonic(s["t0"]),
                               trace.from_monotonic(s["t1"]))
               for s in rounds)
    return 100.0 * sum(parts) / busy if busy > 0.0 else None
