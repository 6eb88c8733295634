"""lightning_update_roofline (layer: kernels) - the linear-attention layers'
recurrence step's share of its roofline, over the profiled sub-window, in
percent: `ssm_update_roofline`'s ratio for a stack whose scanned state is a
linear_attention layer's (that reader counts the layers whose operator holds
"ssm" and a conv's window, which this stack has none of; PERF.md section 7
says which edit folds the two):

    least seconds the chip could take for the updates made  /  device
    seconds of the operations under the program's `ssm_update` scope

The updates: one per linear_attention layer (the meta's `layer_types`) per
decode TRIP of every dispatch inside the sub-window, over the streams LIVE at
the dispatch: a live slot's state [ssm_heads, ssm_head_dim, ssm_state] read
once and written once.  Operations and bytes: benchmark/costs_sparse.py;
peaks: benchmark/peaks.py.  Memory binds.  A program with no such scope or
meta gives no reading."""

from benchmark import costs, costs_sparse, moe_trace, peaks, ssm_trace


def read(spans, trace, run):
    meta = run["meta"]
    layers = list(meta.get("layer_types") or ()).count("linear_attention")
    rounds = moe_trace.rounds_in_profile(spans, run)
    busy = moe_trace.scope_seconds(trace, run, rounds, "ssm_update")
    if not layers or busy is None or busy <= 0.0:
        return None
    flops = bytes_ = 0.0
    for step in rounds:
        left = [n for _, n in ssm_trace.live_streams(run, step)]
        for trip in range(int(step["attrs"].get("trips") or 1)):
            f, b = costs_sparse.linear_update_cost(
                sum(1 for n in left if trip < n), int(meta["ssm_heads"]),
                int(meta["ssm_head_dim"]), int(meta["ssm_state"]))
            flops += f * layers
            bytes_ += b * layers
    if bytes_ <= 0.0:
        return None
    pk = peaks.peaks_for(run["device_kind"])
    least, _bound = costs.roofline_seconds(
        flops, bytes_, pk["flops_per_s"]["float32_default_precision"],
        pk["hbm_bytes_per_s"])
    return 100.0 * least / busy
