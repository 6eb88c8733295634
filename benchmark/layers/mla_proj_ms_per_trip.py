"""mla_proj_ms_per_trip (layer: kernels) - device time of latent attention's
projections around its kernel (the query's and the row's down-projections
and norms, the query's up-projection and rotation, the absorption of the
key up-projection into the query, the value up-projection of the weighted
latents, the output projection: the operations under the program's
`mla_proj` scope, all layers) per decode TRIP, over the dispatches that lie
inside the profiled sub-window, in ms.  How the operations are found:
benchmark/moe_trace.py; a program with no such scope gives no reading."""

from benchmark import moe_trace


def read(spans, trace, run):
    rounds = moe_trace.rounds_in_profile(spans, run)
    busy = moe_trace.scope_seconds(trace, run, rounds, "mla_proj")
    trips = sum(int(s["attrs"].get("trips") or 1) for s in rounds)
    if busy is None or busy <= 0.0 or not trips:
        return None
    return 1e3 * busy / trips
