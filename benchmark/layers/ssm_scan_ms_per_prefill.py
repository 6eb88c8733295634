"""ssm_scan_ms_per_prefill (layer: kernels) - device time of the state-space
mixers' chunked scan (the conv over the prompt, the chunks' quadratic forms,
the carried state: the operations under the program's `ssm_scan` scope, all
layers) per PREFILL, over the prefills that lie inside the profiled
sub-window, in ms.  A prefill is one `serving/prefill_compute` span; its
bucket is the smallest of the meta's `prefill_buckets` that holds its
`prompt`, and its operations are those the driver named from that bucket's
own executable (`run["scope_ops"]["ssm_scan@<bucket>"]`:
drivers/serve_decode_ssm.py).  A program with no such scope gives no
reading."""

from benchmark import spans as sp
from benchmark import xplane


def read(spans, trace, run):
    ops = run.get("scope_ops", {})
    buckets = sorted(int(b) for b in run["meta"].get("prefill_buckets", ()))
    m0, m1 = run["trace_window_monotonic"]
    busy, n = 0.0, 0
    for s in sp.named(spans, "serving/prefill_compute", (m0, m1)):
        bucket = next((b for b in buckets
                       if int(s["attrs"].get("prompt") or 0) <= b), None)
        names = set(ops.get("ssm_scan@%s" % bucket, ()))
        if s["t1"] > m1 or not names:
            continue
        busy += trace.matching_seconds(
            trace.from_monotonic(s["t0"]), trace.from_monotonic(s["t1"]),
            lambda text: xplane.short_name(text) in names)
        n += 1
    return 1e3 * busy / n if n and busy > 0.0 else None
