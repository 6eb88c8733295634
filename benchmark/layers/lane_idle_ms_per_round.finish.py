"""lane_idle_ms_per_round.finish (layer: scheduler) - device idle time per
decode round under the program's `serving/finish` spans (a request's terminal
transition: last flush, the slot's release `serving/slot_free`, request spans,
metrics, the stream's end), over the rounds of the profiled sub-window: the
chip waiting for the dispatch that ends a wave.  One of the four parts of
`decode_idle_ms_per_round.lane` (`benchmark/lane_detail.py`).  None for a
program without the span."""

from benchmark import lane_detail


def read(spans, trace, run):
    return lane_detail.idle_ms_per_round(spans, trace, run, "serving/finish",
                                         needs="serving/finish")
