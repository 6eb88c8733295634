"""feed_gap_ms_per_step (layer: trainer front) - what a step costs beyond
the device's own work: the benchmark's span around `Executor.run` (host
clock, averaged over the whole calls inside the profiled sub-window) minus
the device busy time per step from the device trace.  It holds the feed's
host-to-device copy, dispatch and the fetch, as far as they are not hidden
behind the device."""


def read(spans, trace, run):
    if not run.get("calls_window") or not run.get("steps_in_trace"):
        return None
    c0, c1 = run["calls_window"]
    steps = run["steps_in_trace"]
    return (run["call_seconds_in_trace"] - trace.busy_mean(c0, c1)) \
        / steps * 1e3
