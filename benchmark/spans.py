"""Small helpers the per-layer readers share.  A span is a dict
{"name", "t0", "t1", "attrs"} with times on time.monotonic()'s clock:
the program's own spans (paddle_tpu.obs.tracing) and the benchmark's
(`bench/...`)."""

from benchmark import stats


def named(spans, name, window=None):
    """Spans called `name`, that began inside `window` = (t0, t1) if given."""
    return [s for s in spans if s["name"] == name
            and (window is None or window[0] <= s["t0"] <= window[1])]


def durations_ms(spans, name, window=None):
    return [(s["t1"] - s["t0"]) * 1e3 for s in named(spans, name, window)]


def percentile_ms(spans, name, q, window=None):
    d = durations_ms(spans, name, window)
    return stats.percentile(d, q) if d else None
