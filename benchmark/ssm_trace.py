"""What the readers of a stack with state-space mixers share: a scope's
device time per decode trip, and the streams live at a dispatch.  A file of
its own beside moe_trace.py, which a PR that adds a configuration may not
edit."""

import bisect

from benchmark import moe_trace


def scope_ms_per_trip(spans, trace, run, scope):
    """Device ms of the operations under the program's `scope` per decode
    TRIP, over the dispatches that lie inside the profiled sub-window (a
    dispatch's `trips` ride its `serving/decode_step` span); None where the
    run names no operation of that scope (benchmark/moe_trace.py)."""
    rounds = moe_trace.rounds_in_profile(spans, run)
    busy = moe_trace.scope_seconds(trace, run, rounds, scope)
    trips = sum(int(s["attrs"].get("trips") or 1) for s in rounds)
    if busy is None or busy <= 0.0 or not trips:
        return None
    return 1e3 * busy / trips


def live_streams(run, step):
    """[(positions held, tokens still to come)] of the streams live at the
    dispatch `step` (a `serving/decode_step` span), rebuilt from the
    generator's records as `gqa_attention_roofline` rebuilds them: a
    stream's prompt + the tokens it had received when the dispatch began."""
    live = []
    for r in run["records"]:
        tt = r.token_times
        if tt and tt[0] <= step["t0"] and (r.done is None
                                           or r.done >= step["t1"]):
            have = bisect.bisect_right(tt, step["t0"])
            if have < r.max_new:
                live.append((r.prompt_len + have, r.max_new - have))
    return live
