"""decode_h2d_bytes_per_round (layer: decode phases) - host-to-device bytes
of one decode dispatch: the median `h2d_bytes` of the program's
`decode/launch` spans of `phase=step` over the measured window - every
argument leaf of the step executable that was not on a device when it was
called, the weights among them under the default placement.  The same by
phase, and the median milliseconds of every `decode/*` and `serving/*` span,
go on an earlier output line."""

import json

from benchmark import idle, stats


def read(spans, trace, run):
    w0, w1 = run["window"]
    by_phase = {}
    for s in spans:
        if s["name"] == "decode/launch" and w0 <= s["t0"] <= w1 \
                and "h2d_bytes" in s["attrs"]:
            by_phase.setdefault(s["attrs"].get("phase"), []).append(
                s["attrs"]["h2d_bytes"])
    if "step" not in by_phase:
        return None
    print(json.dumps({
        "phase": "decode_counters",
        "launch_h2d_bytes_p50": {str(k): stats.median(v)
                                 for k, v in by_phase.items()},
        "span_ms_p50_and_count": idle.span_medians(spans, (w0, w1))}),
        flush=True)
    return stats.median(by_phase["step"])
