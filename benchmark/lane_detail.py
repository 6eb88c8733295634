"""What the lane and the stream handlers do between two dispatches, read
from the program's own spans: the arithmetic the `lane_idle_ms_per_round.*`,
`finish_ms_per_ender`, `slot_free_ms_per_ender` and `token_out_ms_per_frame.*`
readers share (PERF.md section 3, scheduler and serving front).

`decode_idle_ms_per_round.lane` is ONE number: the device's idle time under
`serving/lane_iter` outside the `decode/*` phases.  The spans inside a lane
iteration cut it four ways: under `serving/prefill_compute` (a prefill's host
side), under `serving/emit` outside a finish (the puts of a delivery), under
`serving/finish` (a request's terminal transition, its `serving/slot_free`
included) and what is left under `serving/lane_iter` (admission take,
decision, notify).  The cut is `idle.idle_split` over the same gaps and the
same rounds as `idle.decode_round_split`, so the four sum to that number
wherever the named spans lie inside a lane iteration, as the lane emits them.

`finish_ms_per_ender` and `slot_free_ms_per_ender` are the mean milliseconds
of `serving/finish` / `serving/slot_free` over the measured window.  The
stream handlers land one `serving/stream_out` span a request;
`token_out_ms_per_frame.lane` / `.wake` / `.send` are the window's
`lane_ms_sum` / `wake_ms_sum` / `send_ms_sum` over its `frames`: the
program's side of what `token_wire_ms_p50` times from outside.  All nine
run in the five decode cells and move `tokens_per_s` (OBSERVABILITY.md,
"Who reads what").  The three span names begin with `serving/`, so
`idle.span_table` puts their median, mean and count on every UNTRACED run's
`window` line too.

A program without `serving/emit`'s children or `serving/stream_out` (the
parent of the PR that added them) gives `.emit`, `.finish` and the five
span readers nothing: None.  `.prefill_host` and `.other` read what they
always could.
"""

from benchmark import idle, spans as sp

LANE_SPANS = ("serving/lane_iter", "serving/prefill_compute",
              "serving/emit", "serving/finish")


def idle_ms_per_round(spans, trace, run, name, needs=None):
    """Device idle milliseconds a decode round whose innermost span, of
    `decode/*` and LANE_SPANS, is `name`, over the profiled sub-window.
    None without the `decode/*` spans, or where the program emits no span
    called `needs`."""
    got = idle.decode_round_split(spans, trace, run)
    if got is None:
        return None
    if needs is not None and not any(s["name"] == needs for s in spans):
        return None
    mine = idle.on_trace_clock(spans, trace, idle.DECODE_SPANS + LANE_SPANS)
    by_name, _ = idle.idle_split(run, trace, run["trace_window"], mine,
                                 "lane_detail")
    return by_name.get(name, 0.0) / got[1] * 1e3


def mean_ms(spans, run, name):
    """Mean milliseconds of the spans called `name` that began inside the
    measured window; None where there is none."""
    ms = sp.durations_ms(spans, name, run["window"])
    return sum(ms) / len(ms) if ms else None


def stream_out_ms_per_frame(spans, run, attr):
    """Sum of attribute `attr` over the sum of `frames` of the
    `serving/stream_out` spans that began inside the measured window: the
    mean over the window's chunk frames of one part of a frame's way out."""
    outs = [s["attrs"] for s in sp.named(spans, "serving/stream_out",
                                         run["window"])
            if attr in s["attrs"]]
    frames = sum(int(a.get("frames") or 0) for a in outs)
    return sum(float(a[attr]) for a in outs) / frames if frames else None
