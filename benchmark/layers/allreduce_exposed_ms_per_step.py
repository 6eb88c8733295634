"""allreduce_exposed_ms_per_step (layer: collectives) - time per step in
which a collective operation (all-reduce and kin) ran on a chip and no other
operation did, averaged over the chips: the part of the gradient exchange
the backward pass does not hide."""


def read(spans, trace, run):
    if not run.get("calls_window") or not run.get("steps_in_trace"):
        return None
    c0, c1 = run["calls_window"]
    return trace.exposed_seconds(c0, c1) / run["steps_in_trace"] * 1e3
