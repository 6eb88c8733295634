"""sparse_attention_roofline (layer: kernels) - the Mosaic kernel
`sparse_decode_attention`'s share of its roofline, over the profiled
sub-window, in percent:

    least seconds the chip could take for the calls made  /  device seconds
    of the kernel's events in the trace

The calls: one per sparse_attention layer (the meta's `layer_types`) per
decode TRIP of every dispatch inside the sub-window, over the streams LIVE at
the dispatch (rebuilt from the generator's records, as
`mixed_attention_roofline` rebuilds them): the K and V rows of a slot's
SELECTED blocks (all of its rows while no more than `sparse_topk` blocks are
in sight), q in and the result out.  Operations and bytes:
benchmark/costs_sparse.py (the WORK, the same count whatever implements the
kernel); peaks: benchmark/peaks.py.  Memory binds.  The kernel's events are
found by the configuration's `kernel_trace_match.sparse_attention` substring
of the instruction's name.  A program with no such meta, or a run with no
such event, gives no reading."""

from benchmark import costs, costs_sparse, peaks, ssm_trace
from benchmark import spans as sp


def read(spans, trace, run):
    match = run.get("kernel_match", {}).get("sparse_attention")
    meta = run["meta"]
    layers = list(meta.get("layer_types") or ()).count("sparse_attention")
    if not match or not layers:
        return None
    w0, w1 = run["trace_window"]
    busy = trace.matching_seconds(w0, w1, lambda n: match in n)
    if busy <= 0.0:
        return None
    m0, m1 = run["trace_window_monotonic"]
    heads, kv_heads = int(meta["n_heads"]), int(meta["n_kv_heads"])
    dh = int(meta.get("head_dim") or int(meta["d_model"]) // heads)
    flops = bytes_ = 0.0
    for step in sp.named(spans, "serving/decode_step", (m0, m1)):
        if step["t1"] > m1:
            continue
        live = ssm_trace.live_streams(run, step)
        for trip in range(int(step["attrs"].get("trips") or 1)):
            f, b = costs_sparse.sparse_attention_cost(
                [n + trip + 1 for n, left in live if trip < left], layers,
                heads, kv_heads, dh, int(meta["sparse_block"]),
                int(meta["sparse_topk"]))
            flops, bytes_ = flops + f, bytes_ + b
    if bytes_ <= 0.0:
        return None
    pk = peaks.peaks_for(run["device_kind"])
    least, _bound = costs.roofline_seconds(
        flops, bytes_, pk["flops_per_s"]["float32_default_precision"],
        pk["hbm_bytes_per_s"])
    return 100.0 * least / busy
