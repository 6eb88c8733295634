"""token_out_frames_per_pass (layer: serving front) - the chunk frames one
pass of the server's writer thread sends: the mean `frames` of the program's
`serving/write_pass` spans (one a pass of the ONE thread that sends every
stream's frames; a pass takes everything the lanes have queued, and a
delivery queues its chunks as one item) that began inside the measured
window.  It says whether a delivery's frames leave on one wake-up: about the
number of live streams a delivery, less for the passes that carry one
prefill's first token; a thread a stream, as before the writer, is 1 by
construction.  None for a program without the span."""

from benchmark import spans as sp


def read(spans, trace, run):
    frames = [int(s["attrs"].get("frames") or 0)
              for s in sp.named(spans, "serving/write_pass", run["window"])]
    return sum(frames) / len(frames) if frames else None
