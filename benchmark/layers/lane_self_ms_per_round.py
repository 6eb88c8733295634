"""lane_self_ms_per_round (layer: scheduler) - the lane loop's own host time
per decode round: the program's `serving/lane_iter` spans minus their
children `serving/decode_step` and `serving/prefill_compute`, over the
rounds, mean over the measured window."""

from benchmark import idle


def read(spans, trace, run):
    got = idle.lane_sums(spans, run["window"])
    if got is None or not got[3]:
        return None
    lane, steps, prefills, rounds = got
    return (lane - steps - prefills) / rounds * 1e3
