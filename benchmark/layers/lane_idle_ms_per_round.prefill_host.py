"""lane_idle_ms_per_round.prefill_host (layer: scheduler) - device idle time
per decode round under the program's `serving/prefill_compute` span and under
no `decode/*` span, over the rounds of the profiled sub-window: the chip
waiting for the host side of a prefill (padding the prompt, landing its rows in
the slot, the bookkeeping around the call).  One of the four parts of
`decode_idle_ms_per_round.lane` (`benchmark/lane_detail.py`)."""

from benchmark import lane_detail


def read(spans, trace, run):
    return lane_detail.idle_ms_per_round(spans, trace, run,
                                         "serving/prefill_compute")
