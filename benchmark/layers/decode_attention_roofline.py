"""decode_attention_roofline (layer: kernels) - the Mosaic `decode_attention`
kernel's share of its roofline over the profiled sub-window, in percent:

    least seconds the chip could take for the calls made  /  device seconds
    of the kernel's events in the trace

The calls: one per layer per decode TRIP of every dispatch inside the
sub-window - a dispatch's `trips` ride its `serving/decode_step` span (a
span without them is one step), and every live stream is a token longer at
each trip until its budget ends.  A stream's length at a dispatch is rebuilt
from the generator's records (its prompt + the tokens it had received).
Counted once a span, as before PR 34, the share read about 1/`trips` of the
kernel's.
Operations and bytes per call: benchmark/costs.py; peaks: benchmark/peaks.py.
The binding bound is memory (attention over a cache at batch 1 per slot
reads 2 x 4 bytes per 4 FLOPs), logged beside the value."""

import bisect

from benchmark import costs, peaks
from benchmark import spans as sp


def read(spans, trace, run):
    match = run.get("kernel_match", {}).get("decode_attention")
    if not match:
        return None
    w0, w1 = run["trace_window"]
    busy = trace.matching_seconds(w0, w1, lambda n: match in n)
    if busy <= 0.0:
        return None
    m0, m1 = run["trace_window_monotonic"]
    meta = run["meta"]
    heads = int(meta["n_heads"])
    dh = int(meta["d_model"]) // heads
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
            f, b = costs.decode_attention_cost(
                [n + trip for n, left in live if trip < left], heads, dh)
            flops += f * int(meta["n_layers"])
            bytes_ += b * int(meta["n_layers"])
    if bytes_ <= 0.0:
        return None
    pk = peaks.peaks_for(run["device_kind"])
    least, _bound = costs.roofline_seconds(
        flops, bytes_, pk["flops_per_s"]["float32_default_precision"],
        pk["hbm_bytes_per_s"])
    return 100.0 * least / busy
