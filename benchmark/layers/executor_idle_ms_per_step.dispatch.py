"""executor_idle_ms_per_step.dispatch (layer: trainer front) - device idle
time per training step that falls under the program's `executor/dispatch`
span (scope lookups, the executable's cache lookup, the jitted call until it
returns), over the whole calls of the profiled sub-window."""

from benchmark import idle


def read(spans, trace, run):
    got = idle.executor_step_split(trace, run)
    if got is None:
        return None
    by_name, steps = got
    return by_name.get("executor/dispatch", 0.0) / steps * 1e3
