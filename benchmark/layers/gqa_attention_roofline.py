"""gqa_attention_roofline (layer: kernels) - the Mosaic `decode_attention`
kernel's share of its roofline in a stack whose attention is GROUPED-QUERY
and only some of whose layers attend, over the profiled sub-window, in
percent:

    least seconds the chip could take for the calls made  /  device seconds
    of the kernel's events in the trace

The calls: one per ATTENTION layer (the meta's `layer_types`) per decode
TRIP of every dispatch inside the sub-window - a dispatch's `trips` ride its
`serving/decode_step` span, and every live stream is a token longer at each.
A stream's length at a dispatch is rebuilt from the generator's records (its
prompt + the tokens it had received).  Operations and bytes per call, by the
K/V heads for the rows and the query heads for the rest:
benchmark/costs_hybrid.py; peaks: benchmark/peaks.py.  Memory binds.  A
program with no such meta, or a run with no such kernel event, gives no
reading."""

import bisect

from benchmark import costs, costs_hybrid, peaks
from benchmark import spans as sp


def read(spans, trace, run):
    match = run.get("kernel_match", {}).get("gqa_attention")
    meta = run["meta"]
    if not match or not meta.get("n_kv_heads"):
        return None
    w0, w1 = run["trace_window"]
    busy = trace.matching_seconds(w0, w1, lambda n: match in n)
    if busy <= 0.0:
        return None
    m0, m1 = run["trace_window_monotonic"]
    heads, kv_heads = int(meta["n_heads"]), int(meta["n_kv_heads"])
    dh = int(meta["d_model"]) // heads
    kinds = meta.get("layer_types") or ["attention"] * int(meta["n_layers"])
    layers = sum(1 for k in kinds if k == "attention")
    flops = bytes_ = 0.0
    for step in sp.named(spans, "serving/decode_step", (m0, m1)):
        if step["t1"] > m1:
            continue
        live = []
        for r in run["records"]:
            tt = r.token_times
            if tt and tt[0] <= step["t0"] and (r.done is None
                                               or r.done >= step["t1"]):
                have = bisect.bisect_right(tt, step["t0"])
                if have < r.max_new:
                    live.append((r.prompt_len + have, r.max_new - have))
        for trip in range(int(step["attrs"].get("trips") or 1)):
            f, b = costs_hybrid.gqa_attention_cost(
                [n + trip for n, left in live if trip < left],
                heads, kv_heads, dh)
            flops += f * layers
            bytes_ += b * layers
    if bytes_ <= 0.0:
        return None
    pk = peaks.peaks_for(run["device_kind"])
    least, _bound = costs.roofline_seconds(
        flops, bytes_, pk["flops_per_s"]["float32_default_precision"],
        pk["hbm_bytes_per_s"])
    return 100.0 * least / busy
