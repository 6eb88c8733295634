"""decode_trips_per_dispatch (layer: decode phases) - decode steps one
dispatch of the step executable ran: the mean `trips` attribute of the
program's `decode/fetch` spans of `phase=step` in the measured window.  A
program whose step is one decode step a dispatch stamps no `trips`, and
each of its fetches counts as one trip: 1.0.  Towards the step
executable's window (`decode.STEP_WINDOW`) where the lane runs windows
(every slot assigned: PERF.md section 6, PR 30).  No such span, no
reading."""

from benchmark import spans as sp


def read(spans, trace, run):
    trips = [s["attrs"].get("trips", 1)
             for s in sp.named(spans, "decode/fetch", run["window"])
             if s["attrs"].get("phase") == "step"]
    return sum(trips) / float(len(trips)) if trips else None
