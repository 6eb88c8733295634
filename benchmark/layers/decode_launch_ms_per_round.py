"""decode_launch_ms_per_round (layer: decode phases) - host time to get one
decode round onto the device: the program's `decode/put` + `decode/launch`
spans of `phase=step` (the small arguments' upload, then the executable call
until it returns) summed per round, median over the measured window."""

from benchmark import idle, stats


def read(spans, trace, run):
    ms = idle.step_phase_ms(spans, run["window"],
                            ("decode/put", "decode/launch"))
    return stats.median(ms) if ms else None
