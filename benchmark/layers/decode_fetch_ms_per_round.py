"""decode_fetch_ms_per_round (layer: decode phases) - the wait for the
device and the token fetch of one decode round: the program's `decode/fetch`
spans of `phase=step` summed per round, median over the measured window."""

from benchmark import idle, stats


def read(spans, trace, run):
    ms = idle.step_phase_ms(spans, run["window"], ("decode/fetch",))
    return stats.median(ms) if ms else None
