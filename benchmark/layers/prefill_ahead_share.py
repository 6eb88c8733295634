"""prefill_ahead_share (layer: scheduler) - the share of the lane's prefills
that were launched AHEAD of the fetch of the prefill before them, in percent:
the program's `serving/prefill_compute` spans of the measured window whose
`ahead` attribute is 1, over those that carry the attribute.  An admission of
P prompts is a pipeline one deep (`DecodeBatcher._admit`): each prompt but
the first is queued on the device while the one ahead of it still runs, so an
admission reads (P - 1) / P and a lane that admits one prompt a pass reads 0
(PERF.md section 6, PR 53).  A program whose spans carry no such attribute,
as every one before that PR, gives no reading."""

from benchmark import spans as sp


def read(spans, trace, run):
    flags = [int(s["attrs"]["ahead"])
             for s in sp.named(spans, "serving/prefill_compute",
                               run["window"])
             if "ahead" in s["attrs"]]
    return 100.0 * sum(flags) / len(flags) if flags else None
