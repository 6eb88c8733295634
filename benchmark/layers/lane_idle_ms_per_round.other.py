"""lane_idle_ms_per_round.other (layer: scheduler) - device idle time per
decode round under the program's `serving/lane_iter` span and under none of
`decode/*`, `serving/prefill_compute`, `serving/emit`, `serving/finish`, over
the rounds of the profiled sub-window: the admission take, the decision, the
notify.  One of the four parts of `decode_idle_ms_per_round.lane`
(`benchmark/lane_detail.py`); for a program without `serving/finish` it holds
nothing more than it does with it."""

from benchmark import lane_detail


def read(spans, trace, run):
    return lane_detail.idle_ms_per_round(spans, trace, run,
                                         "serving/lane_iter")
