"""decode_idle_ms_per_round.launch (layer: decode phases) - device idle time
per decode round that falls under the program's `decode/put` and
`decode/launch` spans (any phase), over the rounds of the profiled
sub-window: the chip waiting while the host uploads arguments and launches."""

from benchmark import idle


def read(spans, trace, run):
    got = idle.decode_round_split(spans, trace, run)
    if got is None:
        return None
    by_name, rounds = got
    return (by_name.get("decode/put", 0.0)
            + by_name.get("decode/launch", 0.0)) / rounds * 1e3
