"""executor_idle_ms_per_step.feed (layer: trainer front) - device idle time
per training step that falls under the program's `executor/feed` span (host
dtype casts and the feed's host-to-device copy), over the whole calls of
the profiled sub-window."""

from benchmark import idle


def read(spans, trace, run):
    got = idle.executor_step_split(trace, run)
    if got is None:
        return None
    by_name, steps = got
    return by_name.get("executor/feed", 0.0) / steps * 1e3
