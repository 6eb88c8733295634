"""slots_busy_share (layer: scheduler) - share of slot-steps that emitted a
token: the `tokens` of the program's `serving/decode_step` spans over
(decode steps x decode_slots) in the measured window, in percent.  Since
PR 30 a span is a DISPATCH of `trips` decode steps (a span without the
attribute is one step), so a slot can emit `trips` tokens a span; divided by
the spans alone the share read ~700% (PR 30-33)."""

from benchmark import spans as sp


def read(spans, trace, run):
    steps = sp.named(spans, "serving/decode_step", run["window"])
    if not steps:
        return None
    tokens = sum(int(s["attrs"].get("tokens") or 0) for s in steps)
    trips = sum(int(s["attrs"].get("trips") or 1) for s in steps)
    return 100.0 * tokens / (trips * run["slots"])
