"""mla_attention_roofline (layer: kernels) - the Mosaic
`latent_decode_attention` kernel's share of its roofline over the profiled
sub-window, in percent:

    least seconds the chip could take for the calls made  /  device seconds
    of the kernel's events in the trace

The calls: one per layer (every layer of the stack is latent attention) per
decode TRIP of every dispatch inside the sub-window - a dispatch's `trips`
ride its `serving/decode_step` span, and every live stream is a token longer
at each.  A stream's length at a dispatch is rebuilt from the generator's
records (its prompt + the tokens it had received).  Operations and bytes per
call - the live rows read ONCE for all heads at the table's 4 bytes a value,
2 * (row + value lanes) * heads FLOP a live position: benchmark/costs_mla.py;
peaks: benchmark/peaks.py (the kernel's matmuls take bfloat16 operands: the
bf16 peak).  The kernel's events are found by the configuration's
`kernel_trace_match.mla_attention` (the stack's one Pallas call; its prefill
expands the rows and runs no kernel).  A program with no such meta, or a run
with no such event, gives no reading."""

import bisect

from benchmark import costs, costs_mla, peaks
from benchmark import spans as sp


def read(spans, trace, run):
    match = run.get("kernel_match", {}).get("mla_attention")
    meta = run["meta"]
    if not match or not meta.get("kv_lora_rank"):
        return None
    w0, w1 = run["trace_window"]
    busy = trace.matching_seconds(w0, w1, lambda n: match in n)
    if busy <= 0.0:
        return None
    m0, m1 = run["trace_window_monotonic"]
    heads, values = int(meta["n_heads"]), int(meta["kv_lora_rank"])
    row = values + int(meta["qk_rope_head_dim"])
    layers = int(meta["n_layers"])
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
            f, b = costs_mla.latent_attention_cost(
                [n + trip for n, left in live if trip < left],
                heads, row, values)
            flops += f * layers
            bytes_ += b * layers
    if bytes_ <= 0.0:
        return None
    pk = peaks.peaks_for(run["device_kind"])
    least, _bound = costs.roofline_seconds(
        flops, bytes_, pk["flops_per_s"]["bfloat16"], pk["hbm_bytes_per_s"])
    return 100.0 * least / busy
