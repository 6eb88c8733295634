"""decode_idle_ms_per_round.lane (layer: scheduler) - device idle time per
decode round that falls under the program's `serving/lane_iter` span and
under no `decode/*` span, over the rounds of the profiled sub-window: the
chip waiting for the lane's own work (token emission to the streams,
admission, a prefill's host side, the session's bookkeeping)."""

from benchmark import idle


def read(spans, trace, run):
    got = idle.decode_round_split(spans, trace, run)
    if got is None:
        return None
    by_name, rounds = got
    return by_name.get("serving/lane_iter", 0.0) / rounds * 1e3
