"""Device idle time split by what the host was doing: the arithmetic the
`decode_idle_ms_per_round.*` and `executor_idle_ms_per_step.*` readers share.

The program's spans (paddle_tpu.obs.tracing: `executor/*`, `decode/*`,
`serving/lane_iter`) carry their start on time.monotonic(), the clock the
device trace is anchored to, so each can be laid over the trace's idle gaps
(`xplane.Trace.idle_gaps`).  A moment of idle time belongs to the INNERMOST
span that covers it - of the covering spans, the one that began last - and
to no span if none does.  Readers name the spans that take part; a span
left out gives its time to the span around it.

A program without these spans (the parent of the PR that added them) yields
empty lists and the readers return None.
"""

import bisect
import json

from benchmark import stats


def innermost_timeline(spans):
    """[(name, start, end)] in any order and nesting -> disjoint sorted
    [(start, end, name)]: every stretch some span covers, named by the
    covering span that began last (the shortest of those that began
    together)."""
    cuts = sorted({t for _, s, e in spans if e > s for t in (s, e)})
    rows = sorted((s, e, n) for n, s, e in spans if e > s)
    out, live, k = [], [], 0
    for a, b in zip(cuts, cuts[1:]):
        while k < len(rows) and rows[k][0] <= a:
            live.append(rows[k])
            k += 1
        live = [r for r in live if r[1] > a]
        if not live:
            continue
        _, _, name = max(live, key=lambda r: (r[0], -r[1]))
        if out and out[-1][2] == name and out[-1][1] == a:
            out[-1] = (out[-1][0], b, name)
        else:
            out.append((a, b, name))
    return out


def split(gaps, spans):
    """({name: seconds}, seconds under no span): the idle `gaps`
    [(start, end)] shared out over `spans` [(name, start, end)], innermost
    span first."""
    line = innermost_timeline(spans)
    starts = [s for s, _, _ in line]
    by_name, total = {}, 0.0
    for gs, ge in gaps:
        total += ge - gs
        i = max(bisect.bisect_right(starts, gs) - 1, 0)
        while i < len(line) and line[i][0] < ge:
            s, e, name = line[i]
            cov = min(e, ge) - max(s, gs)
            if cov > 0.0:
                by_name[name] = by_name.get(name, 0.0) + cov
            i += 1
    return by_name, total - sum(by_name.values())


def idle_split(run, trace, window, spans, what):
    """`split` of the trace's idle gaps inside `window` (trace clock).  The
    readers of one run share it: it is worked out once, kept in `run`, and
    put on an earlier output line with the share of the idle time that no
    span covers."""
    done = run.setdefault("idle_split", {})
    if what not in done:
        a, b = window
        by_name, bare = split(trace.idle_gaps(a, b), spans)
        idle = sum(by_name.values()) + bare
        done[what] = (by_name, bare)
        print(json.dumps({
            "phase": "idle_split", "what": what, "window_s": b - a,
            "idle_s": idle, "idle_s_by_span": by_name,
            "idle_s_under_no_span": bare,
            "share_under_no_span": bare / idle if idle > 0.0 else None,
            "spans": len(spans)}), flush=True)
    return done[what]


def on_trace_clock(spans, trace, names):
    """The serving driver's spans ({"name", "t0", "t1"} on the monotonic
    clock) called one of `names`, as (name, start, end) on the trace's."""
    return [(s["name"], trace.from_monotonic(s["t0"]),
             trace.from_monotonic(s["t1"]))
            for s in spans if s["name"] in names]


# --- serving: a decode round ------------------------------------------------

DECODE_SPANS = ("decode/put", "decode/launch", "decode/fetch")


def rounds_inside(starts, last_end, a, b):
    """How many rounds [a, b] holds, counting the part of a round that
    straddles an edge as that part: a round lasts from its dispatch's start
    to the next one's (the last to its own end).  A whole number of the
    dispatches that BEGAN inside would read a 3 s window of 17.7 rounds as
    17 and every per-round time 4% high."""
    starts = sorted(starts)
    n = 0.0
    for s, e in zip(starts, starts[1:] + [last_end]):
        if e > s:
            n += max(min(e, b) - max(s, a), 0.0) / (e - s)
    return n


def decode_round_split(spans, trace, run):
    """({span name: idle seconds}, rounds) over the profiled sub-window:
    idle under each `decode/*` span (any phase) and under
    `serving/lane_iter` outside them; rounds = the `serving/decode_step`
    dispatches it holds (`rounds_inside`).  None without the program's
    `decode/*` spans."""
    a, b = run["trace_window"]
    mine = on_trace_clock(spans, trace,
                          DECODE_SPANS + ("serving/lane_iter",))
    steps = on_trace_clock(spans, trace, ("serving/decode_step",))
    rounds = rounds_inside([s for _, s, _ in steps],
                           max([e for _, _, e in steps] or [a]), a, b)
    if rounds <= 0.0 or not any(n in DECODE_SPANS for n, _, _ in mine):
        return None
    by_name, _ = idle_split(run, trace, (a, b), mine, "decode_round")
    return by_name, rounds


def step_phase_ms(spans, window, names):
    """[ms per round] of the `phase=step` spans called one of `names`
    that began inside `window` (monotonic), summed by their `round`."""
    by_round = {}
    for i, s in enumerate(spans):
        if (s["name"] in names and s["attrs"].get("phase") == "step"
                and window[0] <= s["t0"] <= window[1]):
            k = s["attrs"].get("round", ("span", i))
            by_round[k] = by_round.get(k, 0.0) + (s["t1"] - s["t0"]) * 1e3
    return list(by_round.values())


def span_table(spans, window, prefixes=("decode/", "serving/")):
    """{name, or name@phase: (median ms, mean ms, count)} of the program's
    spans of those prefixes that began inside `window` (monotonic).  The
    median moves if every span of a kind grew, the mean alone if a few
    grew much."""
    ms = {}
    for s in spans:
        if s["name"].startswith(prefixes) \
                and window[0] <= s["t0"] <= window[1]:
            phase = s["attrs"].get("phase")
            key = s["name"] + ("@" + str(phase) if phase else "")
            ms.setdefault(key, []).append((s["t1"] - s["t0"]) * 1e3)
    return {k: (stats.median(v), sum(v) / len(v), len(v))
            for k, v in sorted(ms.items())}


def span_medians(spans, window, prefixes=("decode/", "serving/")):
    """{name, or name@phase: [median ms, count]}: the table an earlier
    output line carries beside the metrics."""
    return {k: [v[0], v[2]]
            for k, v in span_table(spans, window, prefixes).items()}


def lane_sums(spans, window):
    """Seconds of (`serving/lane_iter`, `serving/decode_step`,
    `serving/prefill_compute`, rounds) over the lane iterations that began
    inside `window` (monotonic); the children are those inside the first
    to the last of these iterations.  None without `serving/lane_iter`."""
    iters = [s for s in spans if s["name"] == "serving/lane_iter"
             and window[0] <= s["t0"] <= window[1]]
    if not iters:
        return None
    lo = min(s["t0"] for s in iters)
    hi = max(s["t1"] for s in iters)

    def inside(name):
        return [s["t1"] - s["t0"] for s in spans if s["name"] == name
                and s["t0"] >= lo and s["t1"] <= hi]
    steps = inside("serving/decode_step")
    return (sum(s["t1"] - s["t0"] for s in iters), sum(steps),
            sum(inside("serving/prefill_compute")), len(steps))


# --- training: a step of Executor.run ---------------------------------------

def executor_spans(trace, run):
    """The program's `executor/*` spans inside the run's `calls_window`, as
    (name, start, end) on the trace's clock.  The training driver hands
    readers its own `bench/...` spans only, so these come from the ring of
    the process that ran the window."""
    if not run.get("calls_window"):
        return []
    from paddle_tpu.obs import tracing
    c0, c1 = run["calls_window"]
    out = []
    for s in tracing.recent_spans():
        if not s["name"].startswith("executor/") or "t0" not in s:
            continue
        a = trace.from_monotonic(s["t0"])
        e = a + s["dur_ms"] * 1e-3
        if a >= c0 and e <= c1:
            out.append((s["name"], a, e))
    return out


def executor_step_split(trace, run):
    """({span name or None: idle seconds}, steps) over the whole training
    calls inside the profiled sub-window; None keys the idle time under no
    `executor/*` span (between two `Executor.run`: the caller's own time).
    None without the program's `executor/*` spans."""
    mine = executor_spans(trace, run)
    if not mine or not run.get("steps_in_trace"):
        return None
    by_name, bare = idle_split(run, trace, run["calls_window"], mine,
                               "executor_step")
    by_name = dict(by_name)
    by_name[None] = bare
    return by_name, run["steps_in_trace"]


def executor_median(run, trace, name, attr=None):
    """Median over the `name` spans that began inside `calls_window` of
    their attr `attr`, or of their milliseconds if none is named; None
    where there is nothing to read."""
    if not run.get("calls_window"):
        return None
    from paddle_tpu.obs import tracing
    c0, c1 = run["calls_window"]
    vals = [s["dur_ms"] if attr is None else s["attrs"][attr]
            for s in tracing.recent_spans(name=name)
            if "t0" in s and (attr is None or attr in s.get("attrs", {}))
            and c0 <= trace.from_monotonic(s["t0"]) <= c1]
    return stats.median(vals) if vals else None
