"""slots_busy_share (layer: scheduler) - share of slot-rounds that emitted a
token: the `tokens` of the program's `serving/decode_step` spans over
(rounds x decode_slots) in the measured window, in percent."""

from benchmark import spans as sp


def read(spans, trace, run):
    steps = sp.named(spans, "serving/decode_step", run["window"])
    if not steps:
        return None
    tokens = sum(int(s["attrs"].get("tokens") or 0) for s in steps)
    return 100.0 * tokens / (len(steps) * run["slots"])
