"""slot_free_ms_per_ender (layer: scheduler) - host milliseconds the release
of one slot takes (`DecodeSession.free`: the calls that zero its state and
give it up): the mean of the program's `serving/slot_free` spans that began
inside the measured window.  The part of `finish_ms_per_ender` that is the
session's.  None for a program without the span."""

from benchmark import lane_detail


def read(spans, trace, run):
    return lane_detail.mean_ms(spans, run, "serving/slot_free")
