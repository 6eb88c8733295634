"""decode_round_ms.deep (layer: decode phases) - median duration of the
program's `serving/decode_step` spans (one dispatch of the fixed-shape step
over the whole slot table, its fetch included) over the measured window of
the deep-context cell."""

from benchmark import spans as sp


def read(spans, trace, run):
    return sp.percentile_ms(spans, "serving/decode_step", 50, run["window"])
