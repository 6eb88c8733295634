"""lane_idle_ms_per_round.emit (layer: scheduler) - device idle time per
decode round under the program's `serving/emit` span outside its
`serving/finish` children, over the rounds of the profiled sub-window: the
chip waiting while the lane hands chunks to the streams' queues.  One of the
four parts of `decode_idle_ms_per_round.lane` (`benchmark/lane_detail.py`).
A program whose deliveries have no `serving/finish` inside them, as every one
before PR 39, gives no reading: its `serving/emit` holds the finishes too."""

from benchmark import lane_detail


def read(spans, trace, run):
    return lane_detail.idle_ms_per_round(spans, trace, run, "serving/emit",
                                         needs="serving/finish")
