"""kinds_attention_roofline (layer: kernels) - the Mosaic `decode_attention`
kernel's share of its roofline in a stack whose attending layers are of two
kinds WITH THEIR OWN GEOMETRIES (meta `window_kv_heads`: the window layers'
K/V heads beside the full layers' `n_kv_heads`; `v_head_dim`: value heads
of another size than the key heads' `head_dim`; `window_sink`: a sink a
query head), over the profiled sub-window, in percent:

    least seconds the chip could take for the calls made  /  device seconds
    of the kernel's events in the trace (both kinds' calls are one kernel)

The calls: per decode TRIP of every dispatch inside the sub-window, one per
full layer over a stream's live rows and one per window layer over
min(live rows, sliding_window) rows of its ring, each kind's K rows and V
rows at their own widths; a stream's length at a dispatch is rebuilt from
the generator's records, as `mixed_attention_roofline` rebuilds it (which
counts ONE geometry for both kinds and is not reported in such a cell).
Operations and bytes per trip: benchmark/costs_kinds.py; peaks:
benchmark/peaks.py.  Memory binds.  A program with no such meta, or a run
with no such kernel event, gives no reading."""

from benchmark import costs, costs_kinds, peaks, ssm_trace
from benchmark import spans as sp


def read(spans, trace, run):
    match = run.get("kernel_match", {}).get("kinds_attention")
    meta = run["meta"]
    kinds = list(meta.get("layer_types") or ())
    window = int(meta.get("sliding_window") or 0)
    if not match or not window or "window_attention" not in kinds \
            or not (meta.get("window_kv_heads") or meta.get("v_head_dim")):
        return None
    w0, w1 = run["trace_window"]
    busy = trace.matching_seconds(w0, w1, lambda n: match in n)
    if busy <= 0.0:
        return None
    m0, m1 = run["trace_window_monotonic"]
    heads = int(meta["n_heads"])
    kv_heads = int(meta.get("n_kv_heads") or heads)
    k_dim = int(meta.get("head_dim") or int(meta["d_model"]) // heads)
    v_dim = int(meta.get("v_head_dim") or k_dim)
    full = (kinds.count("attention"), kv_heads, k_dim, v_dim)
    ring = (kinds.count("window_attention"),
            int(meta.get("window_kv_heads") or kv_heads), k_dim, v_dim)
    flops = bytes_ = 0.0
    for step in sp.named(spans, "serving/decode_step", (m0, m1)):
        if step["t1"] > m1:
            continue
        live = ssm_trace.live_streams(run, step)
        for trip in range(int(step["attrs"].get("trips") or 1)):
            f, b = costs_kinds.kinds_attention_cost(
                [n + trip for n, left in live if trip < left], heads,
                window, full, ring, sink=bool(meta.get("window_sink")))
            flops, bytes_ = flops + f, bytes_ + b
    if bytes_ <= 0.0:
        return None
    pk = peaks.peaks_for(run["device_kind"])
    least, _bound = costs.roofline_seconds(
        flops, bytes_, pk["flops_per_s"]["float32_default_precision"],
        pk["hbm_bytes_per_s"])
    return 100.0 * least / busy
