"""finish_ms_per_ender (layer: scheduler) - host milliseconds one request's
terminal transition takes on the lane thread: the mean of the program's
`serving/finish` spans that began inside the measured window (their sum over
their count).  Times the enders of a wave it is what the dispatch that ends
the wave costs (ROADMAP S1c(4)).  None for a program without the span."""

from benchmark import lane_detail


def read(spans, trace, run):
    return lane_detail.mean_ms(spans, run, "serving/finish")
