"""decode_early_launch_share (layer: scheduler) - the share of the lane's
decode dispatches that were launched AHEAD of the previous dispatch's
delivery, in percent: the program's `serving/decode_step` spans of the
measured window whose `early` attribute is true, over those that carry the
attribute.  A full lane whose last dispatch ended nobody launches the next
one first and hands the tokens to the streams while the device runs; a
dispatch after a finisher, a cancellation, an expiry, an admission, on a lane
with a free slot or a speculative lane does not (PERF.md section 6, PR 38).
A program whose spans carry no such attribute, as every one before that PR,
gives no reading."""

from benchmark import spans as sp


def read(spans, trace, run):
    flags = [bool(s["attrs"]["early"])
             for s in sp.named(spans, "serving/decode_step", run["window"])
             if "early" in s["attrs"]]
    return 100.0 * sum(flags) / len(flags) if flags else None
