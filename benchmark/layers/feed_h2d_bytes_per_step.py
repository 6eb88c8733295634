"""feed_h2d_bytes_per_step (layer: trainer front) - bytes a training step's
feed uploads: the median `h2d_bytes` of the program's `executor/feed` spans
(values that arrived as host arrays; a jax.Array feed counts 0) over the
whole calls of the profiled sub-window.  `cast_bytes` (of those, the bytes
cast on the host first), `state_host_bytes` (state the call had to upload:
should be 0) and the median milliseconds of each `executor/*` span go on an
earlier output line."""

import json

from benchmark import idle


def read(spans, trace, run):
    value = idle.executor_median(run, trace, "executor/feed", "h2d_bytes")
    if value is None:
        return None

    def med(name, attr=None):
        return idle.executor_median(run, trace, "executor/" + name, attr)
    print(json.dumps({
        "phase": "executor_counters", "feed_h2d_bytes": value,
        "feed_cast_bytes": med("feed", "cast_bytes"),
        "state_host_bytes": med("dispatch", "state_host_bytes"),
        "compiled_calls": med("dispatch", "compiled"),
        "d2h_bytes": med("fetch", "d2h_bytes"),
        "span_ms_p50": {n: med(n) for n in ("run", "feed", "dispatch",
                                            "fetch")}}), flush=True)
    return value
