"""hybrid_attention_roofline (layer: kernels) - the Mosaic `decode_attention`
kernel's share of its roofline in a stack whose attention is GROUPED-QUERY,
whose head size is the meta's `head_dim` (not d_model / n_heads) and whose
attending layers are those whose `layer_types` entry holds "attention"
("attention", "attention+ssm"), over the profiled sub-window, in percent:

    least seconds the chip could take for the calls made  /  device seconds
    of the kernel's events in the trace

`gqa_attention_roofline`'s reading with the head size and the K/V-holding
layers taken from the meta (that reader takes d_model // n_heads and counts
`layer_types == "attention"`).  The calls: one per attending layer per decode
TRIP of every dispatch inside the sub-window; a stream's length at a dispatch
is rebuilt from the generator's records.  Operations and bytes per call:
benchmark/costs_hybrid.py (unedited); peaks: benchmark/peaks.py.  Memory
binds.  A program with no such meta, or a run with no such kernel event,
gives no reading."""

from benchmark import costs, costs_hybrid, peaks, ssm_trace
from benchmark import spans as sp


def read(spans, trace, run):
    match = run.get("kernel_match", {}).get("hybrid_attention")
    meta = run["meta"]
    if not match or not meta.get("n_kv_heads"):
        return None
    w0, w1 = run["trace_window"]
    busy = trace.matching_seconds(w0, w1, lambda n: match in n)
    if busy <= 0.0:
        return None
    m0, m1 = run["trace_window_monotonic"]
    heads, kv_heads = int(meta["n_heads"]), int(meta["n_kv_heads"])
    dh = int(meta.get("head_dim") or int(meta["d_model"]) // heads)
    kinds = meta.get("layer_types") or ["attention"] * int(meta["n_layers"])
    layers = sum(1 for k in kinds if "attention" in k)
    flops = bytes_ = 0.0
    for step in sp.named(spans, "serving/decode_step", (m0, m1)):
        if step["t1"] > m1:
            continue
        live = ssm_trace.live_streams(run, step)
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
