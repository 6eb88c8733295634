"""moe_ffn_roofline (layer: kernels) - the routed-expert FFN's share of its
roofline over the decode rounds inside the profiled sub-window, in percent:

    least seconds the chip could take for what those rounds NEEDED  /
    device seconds of the operations under `moe_ffn` in them

What a round needed: per layer, the three matrices of every DISTINCT expert
a live token chose (the `moe_experts_touched` attribute of the round's
`decode/fetch` span, summed over the layers), the router, the live tokens'
rows in and out; FLOPs per (token, expert) pair.  benchmark/costs_moe.py;
peaks benchmark/peaks.py.  Memory binds in the step (a few tokens against
25 MB of weights an expert)."""

from benchmark import costs, costs_moe, moe_trace, peaks
from benchmark import spans as sp


def read(spans, trace, run):
    rounds = moe_trace.rounds_in_profile(spans, run)
    busy = moe_trace.scope_seconds(trace, run, rounds, "moe_ffn")
    if busy is None or busy <= 0.0:
        return None
    meta = run["meta"]
    layers = int(meta["n_layers"])
    flops = bytes_ = 0.0
    for step in rounds:
        fetch = [f for f in sp.named(spans, "decode/fetch",
                                     (step["t0"], step["t1"]))
                 if f["attrs"].get("phase") == "step"
                 and "moe_experts_touched" in f["attrs"]]
        if not fetch:
            return None
        tokens = int(step["attrs"].get("tokens") or 0)
        # per-layer cost at the round's mean experts touched a layer (the
        # cost is linear in it), times the layers
        f, b = costs_moe.moe_ffn_cost(
            tokens, fetch[0]["attrs"]["moe_experts_touched"] / float(layers),
            int(meta["d_model"]), int(meta["expert_width"]),
            int(meta["n_experts"]), int(meta["experts_per_token"]))
        flops += f * layers
        bytes_ += b * layers
    pk = peaks.peaks_for(run["device_kind"])
    least, _bound = costs.roofline_seconds(
        flops, bytes_, pk["flops_per_s"]["float32_default_precision"],
        pk["hbm_bytes_per_s"])
    return 100.0 * least / busy
