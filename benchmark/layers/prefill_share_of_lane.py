"""prefill_share_of_lane (layer: decode phases) - share of the lane's time
that prefills take: the sum of the program's `serving/prefill_compute` spans
over the sum of its `serving/lane_iter` spans in the measured window, in
percent."""

from benchmark import idle


def read(spans, trace, run):
    got = idle.lane_sums(spans, run["window"])
    if got is None or got[0] <= 0.0:
        return None
    return 100.0 * got[2] / got[0]
