"""device_step_ms (layer: program to jit) - device busy time per training
step: the union of the intervals in which an operation ran on a chip
(averaged over the cell's chips), between the start of the first and the end
of the last whole training call inside the profiled sub-window, over the
steps those calls made."""


def read(spans, trace, run):
    if not run.get("calls_window") or not run.get("steps_in_trace"):
        return None
    c0, c1 = run["calls_window"]
    return trace.busy_mean(c0, c1) / run["steps_in_trace"] * 1e3
