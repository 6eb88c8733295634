"""short_conv_ms_per_trip (layer: kernels) - device time of the gated short
convolutions (in-projection, gate, the taps over the slot's conv state, the
state's roll, out-projection: the operations under the program's `short_conv`
scope, all conv layers) per decode TRIP, over the dispatches that lie inside
the profiled sub-window, in ms.  A dispatch's `trips` ride its
`serving/decode_step` span.  How the operations are found:
benchmark/moe_trace.py; a program with no such scope gives no reading."""

from benchmark import moe_trace


def read(spans, trace, run):
    rounds = moe_trace.rounds_in_profile(spans, run)
    busy = moe_trace.scope_seconds(trace, run, rounds, "short_conv")
    trips = sum(int(s["attrs"].get("trips") or 1) for s in rounds)
    if busy is None or busy <= 0.0 or not trips:
        return None
    return 1e3 * busy / trips
