"""held_experts_ffn_roofline (layer: kernels) - the routed-expert FFN's share
of its roofline on a member that HOLDS a run of the experts (meta
`experts_held`), over the decode dispatches inside the profiled sub-window,
in percent:

    least seconds the chip could take for what those dispatches NEEDED  /
    device seconds of the operations under `moe_ffn` in them

What a dispatch needed: per routed layer, the three matrices of every
DISTINCT held expert a live token chose (the `moe_experts_touched` attribute
of the dispatch's `decode/fetch` span, which counts the experts held here,
summed over the routed layers and the trips), at the bytes the run's
`weight_dtype` keeps a weight in; the router's float32 matrix a layer and
trip; the live tokens' rows in and out.  benchmark/costs_mla.py (on
costs_moe.py); peaks benchmark/peaks.py.  Memory binds.  `moe_ffn_roofline`
charges 4 bytes a weight and is not reported in such a cell."""

from benchmark import costs, costs_mla, moe_trace, peaks
from benchmark import spans as sp


def read(spans, trace, run):
    meta = run["meta"]
    if not meta.get("experts_held"):
        return None
    rounds = moe_trace.rounds_in_profile(spans, run)
    busy = moe_trace.scope_seconds(trace, run, rounds, "moe_ffn")
    if busy is None or busy <= 0.0:
        return None
    routed = int(meta["n_layers"]) - int(meta.get("n_dense_layers", 0))
    weight_bytes = 2 if meta.get("weight_dtype") == "bfloat16" else 4
    flops = bytes_ = 0.0
    for step in rounds:
        fetch = [f for f in sp.named(spans, "decode/fetch",
                                     (step["t0"], step["t1"]))
                 if f["attrs"].get("phase") == "step"
                 and "moe_experts_touched" in f["attrs"]]
        if not fetch:
            return None
        trips = int(step["attrs"].get("trips") or 1)
        calls = float(routed * trips)
        # per call (a routed layer of one trip) at the dispatch's mean
        # tokens and held experts touched a call: the cost is linear in both
        f, b = costs_mla.held_experts_ffn_cost(
            int(step["attrs"].get("tokens") or 0) / float(trips),
            fetch[0]["attrs"]["moe_experts_touched"] / calls,
            int(meta["d_model"]), int(meta["expert_width"]),
            int(meta["n_experts"]), weight_bytes)
        flops += f * calls
        bytes_ += b * calls
    pk = peaks.peaks_for(run["device_kind"])
    least, _bound = costs.roofline_seconds(
        flops, bytes_, pk["flops_per_s"]["bfloat16"], pk["hbm_bytes_per_s"])
    return 100.0 * least / busy
