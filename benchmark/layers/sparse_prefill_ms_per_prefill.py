"""sparse_prefill_ms_per_prefill (layer: kernels) - device time of a prefill's
block-sparse attention (stage 1 against the compressed keys and the selection
a block of queries, stage 2's running softmax over the selected blocks'
positions a tile of keys: the operations under the program's `sparse_select`
and `sparse_attention` scopes, all sparse layers and chunks) per PREFILL, over
the prefills that lie inside the profiled sub-window, in ms:
`prefill_attention_ms_per_prefill` with this stack's scopes (that reader names
the window and the full layers' scopes; PERF.md section 7 says which edit
folds the two).  A prefill is one `serving/prefill_compute` span; its bucket
is the smallest of the meta's `prefill_buckets` that holds its `prompt`, and
its operations are those the driver named from that bucket's own executable
(`run["scope_ops"]["<scope>@<bucket>"]`).  A mean over the buckets the
sub-window happened to hold; a program with no such scope gives no reading."""

from benchmark import spans as sp
from benchmark import xplane

SCOPES = ("sparse_select", "sparse_attention")


def read(spans, trace, run):
    ops = run.get("scope_ops", {})
    buckets = sorted(int(b) for b in run["meta"].get("prefill_buckets", ()))
    m0, m1 = run["trace_window_monotonic"]
    busy, n = 0.0, 0
    for s in sp.named(spans, "serving/prefill_compute", (m0, m1)):
        bucket = next((b for b in buckets
                       if int(s["attrs"].get("prompt") or 0) <= b), None)
        names = set()
        for scope in SCOPES:
            names.update(ops.get("%s@%s" % (scope, bucket), ()))
        if s["t1"] > m1 or not names:
            continue
        busy += trace.matching_seconds(
            trace.from_monotonic(s["t0"]), trace.from_monotonic(s["t1"]),
            lambda text: xplane.short_name(text) in names)
        n += 1
    return 1e3 * busy / n if n and busy > 0.0 else None
