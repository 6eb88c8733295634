"""token_wire_ms_p50 (layer: serving front) - from the end of the dispatch
that made a token to its arrival at the client: for each token a client
received inside the measured window (the generator's records), its arrival
minus the end of the newest `serving/decode_step` or
`serving/prefill_compute` span that ended before it; the median.  It holds
the lane's emission, the stream's queue, the wire and the client's read; a
token later than the NEXT dispatch's end would read short."""

import bisect

from benchmark import stats


def read(spans, trace, run):
    ends = sorted(s["t1"] for s in spans
                  if s["name"] in ("serving/decode_step",
                                   "serving/prefill_compute"))
    if not ends:
        return None
    w0, w1 = run["window"]
    waits = []
    for r in run["records"]:
        for t in r.token_times:
            if w0 <= t <= w1:
                i = bisect.bisect_right(ends, t)
                if i:
                    waits.append((t - ends[i - 1]) * 1e3)
    return stats.median(waits) if waits else None
