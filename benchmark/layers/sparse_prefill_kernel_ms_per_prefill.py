"""sparse_prefill_kernel_ms_per_prefill (layer: kernels) - device time of the
flash body of a prefill's block-sparse attention, stage 2 alone (the Mosaic
call `sparse_prefill_attention`, one a chunk a sparse layer: the events whose
instruction NAME holds that substring, whatever scope the executable puts
them under) per PREFILL, over the prefills that lie inside the profiled
sub-window, in ms: `sparse_prefill_ms_per_prefill`'s spans with a predicate on
the name in place of the scopes' names, so the difference of the two is stage
1, the selection and what XLA lays out beside the call.  A prefill is one
`serving/prefill_compute` span that ends inside the sub-window.  A program
without the kernel (stage 2 in plain XLA, or no sparse layer) has no such
event and gives no reading."""

from benchmark import spans as sp
from benchmark import xplane

KERNEL = "sparse_prefill_attention"


def read(spans, trace, run):
    m0, m1 = run["trace_window_monotonic"]
    busy, n = 0.0, 0
    for s in sp.named(spans, "serving/prefill_compute", (m0, m1)):
        if s["t1"] > m1:
            continue
        busy += trace.matching_seconds(
            trace.from_monotonic(s["t0"]), trace.from_monotonic(s["t1"]),
            lambda text: KERNEL in xplane.short_name(text))
        n += 1
    return 1e3 * busy / n if n and busy > 0.0 else None
