"""decode_idle_ms_per_round.fetch (layer: decode phases) - device idle time
per decode round that falls under the program's `decode/fetch` spans (any
phase), over the rounds of the profiled sub-window: the host inside
`np.asarray(out)` while the chip has nothing queued."""

from benchmark import idle


def read(spans, trace, run):
    got = idle.decode_round_split(spans, trace, run)
    if got is None:
        return None
    by_name, rounds = got
    return by_name.get("decode/fetch", 0.0) / rounds * 1e3
