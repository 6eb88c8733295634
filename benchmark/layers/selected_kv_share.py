"""selected_kv_share (layer: kernels) - the sparsity the traffic reaches: the
positions the sparse layers' kernel ATTENDS OVER as a share of the positions
in sight (what a dense layer would read), summed over the running slots of
every trip of the step dispatches in the measured window, in percent:
`selected_rows` over `rows_in_sight` of the program's `decode/fetch` spans of
`phase=step` (counted by the kernel's own rule from the slots' lengths:
`DecodeSession._sparse_stream`).  100 while every stream is under
`sparse_topk` blocks; 4,096 / length past that.  A program whose spans carry
no such counters gives no reading."""

from benchmark import spans as sp


def read(spans, trace, run):
    rows = sight = 0
    for s in sp.named(spans, "decode/fetch", run["window"]):
        a = s["attrs"]
        if a.get("phase") == "step" and "rows_in_sight" in a:
            rows += int(a["selected_rows"])
            sight += int(a["rows_in_sight"])
    return 100.0 * rows / sight if sight else None
