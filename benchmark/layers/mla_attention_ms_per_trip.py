"""mla_attention_ms_per_trip (layer: kernels) - device time of the Mosaic
`latent_decode_attention` kernel (the absorbed attention over the latent
rows alone, all layers) per decode TRIP, over the dispatches that lie inside
the profiled sub-window, in ms.  A dispatch's `trips` ride its
`serving/decode_step` span.  The kernel's events are found by the
configuration's `kernel_trace_match.mla_attention`; a run with no such event
gives no reading."""

from benchmark import moe_trace


def read(spans, trace, run):
    match = run.get("kernel_match", {}).get("mla_attention")
    rounds = moe_trace.rounds_in_profile(spans, run)
    trips = sum(int(s["attrs"].get("trips") or 1) for s in rounds)
    if not match or not trips:
        return None
    busy = sum(trace.matching_seconds(trace.from_monotonic(s["t0"]),
                                      trace.from_monotonic(s["t1"]),
                                      lambda n: match in n) for s in rounds)
    return 1e3 * busy / trips if busy > 0.0 else None
