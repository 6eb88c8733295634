"""executor_idle_ms_per_step.fetch (layer: trainer front) - device idle time
per training step that falls under the program's `executor/fetch` span (the
wait for the step and the fetches' device-to-host copy) or between two
`executor/run` spans (the caller's own time before it calls again), over the
whole calls of the profiled sub-window.  With `.feed` and `.dispatch` it
sums to the idle time of those calls."""

from benchmark import idle


def read(spans, trace, run):
    got = idle.executor_step_split(trace, run)
    if got is None:
        return None
    by_name, steps = got
    return (by_name.get("executor/fetch", 0.0) + by_name.get(None, 0.0)
            + by_name.get("executor/run", 0.0)) / steps * 1e3
