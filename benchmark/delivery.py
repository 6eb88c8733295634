"""The server's count of the tokens it put on the wire beside the clients'
count of the tokens they stamped: the arithmetic the `tokens_sent_per_s`,
`client_read_share` and `token_delivery_ms_mean` readers share (PERF.md
section 3, serving front and load generator; PR 54).

`tokens_per_s` is counted in the generator's process, by arrival stamps.  The
program's side ended at the socket with times and no count, so a lane that
gained tokens a client did not read in time could not be told from a lane that
gained none.  Since PR 54 every `serving/write_pass` span (one a pass of the
server's ONE writer thread) carries `tokens`, those of the chunk frames whose
last byte the pass put on a socket, and `tokens_total`, the writer's running
total after the pass, on `time.monotonic()`: the generator's clock, one for
every process of the machine.  Two curves over the interval `tokens_per_s`
counts in, [w0, w0 + seconds]:

  S(t)  tokens on the wire by t: a step of a pass's `tokens` at the pass's
        START.  A pass's bytes leave after its start, so S runs ahead of the
        truth by at most the pass that is under way, and
  R(t)  tokens stamped by t, from every record's `token_times`, cancelled
        records included (the server sent their tokens too),

obey R <= S.  A run whose R passes S by more than one pass's tokens is not on
one clock, or lost spans: `delivery_inconsistent` on an earlier output line,
and no reading.  From the two:

  tokens_sent_per_s       (S(w1) - S(w0)) / seconds
  client_read_share       100 x R(w1) / (S(w1) - S(w0)); the records are the
                          window's own, so R(w0) = 0
  token_delivery_ms_mean  1e3 x the area between S and R over the window, over
                          the tokens read: by Little's law the mean time from
                          socket to stamp, with no matching of tokens

Worked out once a run, kept in `run` (`curves`), and put on ONE earlier output
line, `delivery`: S and R at each tenth of the window (a client that falls
behind shows as S - R growing tenth by tenth), S - R at its end, and the
lane's own count beside S(w1): the `tokens` of the window's
`serving/decode_step` spans plus a token a `serving/prefill_compute`.

A program whose `serving/write_pass` carries no `tokens` (every one before
PR 54) gives nothing: None, and no line.

One thing here is NOT delivery's own, and goes when a `benchmark` PR repairs
what it stands in for (`_trim_host_spans`, PERF.md section 7): the first
reader of a run leaves in `run["host_spans"]` only the spans that overlap the
profiled sub-window, for `xplane.Trace.breakdown`, which runs after the
readers and tries every host span on every idle gap.
"""

import json

import numpy as np

from benchmark import spans as sp

TENTHS = 10


def curves(spans, run):
    """The run's reduction (a dict: `sent`, `read`, `area_token_s`, ...), or
    None where there is nothing to read or the two counts disagree."""
    if "delivery" not in run:
        _trim_host_spans(run)
        run["delivery"] = _reduce(spans, run)
    return run["delivery"]


def _trim_host_spans(run):
    """Leave in `run["host_spans"]` the spans that overlap the profiled
    sub-window (`run["trace_window"]`, both on the trace's clock).

    `benchmark/run.py` hands that list to `xplane.Trace.breakdown` AFTER the
    readers, and `breakdown` tries every host span on every idle gap of the
    sub-window.  The drivers list the spans of the WHOLE measured window:
    3,760-3,780 of them in `gpt2s_decode_saturated`, where a trace whose
    timestamps leave a hole between nearly all of its 1.09-1.13 million
    operations has 890,000-901,000 gaps (my chip runs, PR 54, parent and
    change alike): 3.4e9 turns of a Python loop, and the run was still in
    `breakdown` when its 1,150 s ended; the driver's check of PR 54 stopped
    the PARENT's traced run there at 1,200 s.  A span that does not overlap
    [w0, w1] covers no gap inside it (`cov <= 0`, never above `best_cov`), and
    the spans that stay keep their order, so `breakdown` names every gap as
    it did and adds them up in the same order: its object is the same to the
    last bit (tests/test_benchmark_delivery.py) in a thirteenth of the turns
    (3,886 -> 288 spans, 82 s: the run ends at 286 s).  The cure
    is `breakdown`'s (a bisection, or this cut where the list is made), which
    only a `benchmark` PR may touch; this goes with it."""
    spans, window = run.get("host_spans"), run.get("trace_window")
    if not spans or not window:
        return
    w0, w1 = window
    kept = [s for s in spans if s[2] > w0 and s[1] < w1]
    if len(kept) < len(spans):
        _say(phase="host_spans_trimmed", had=len(spans), kept=len(kept))
        run["host_spans"] = kept


def _say(**fields):
    print(json.dumps(fields), flush=True)


def _reduce(spans, run):
    passes = sorted((s for s in sp.named(spans, "serving/write_pass")
                     if "tokens" in s["attrs"]), key=lambda s: s["t0"])
    if not passes:
        return None
    seconds = float(run["seconds"])
    w0 = run["window"][0]
    w1 = w0 + seconds
    at = np.array([p["t0"] for p in passes])
    total = np.array([int(p["attrs"]["tokens_total"]) for p in passes])
    # S before each pass: the pass ahead's total; before the first, what the
    # writer had sent when the spans begin (the warm-up's streams)
    before = np.concatenate(([total[0] - int(passes[0]["attrs"]["tokens"])],
                             total[:-1]))
    step = total - before
    stamps = np.sort(np.fromiter(
        (t for r in run["records"] for t in r.token_times), dtype=float))

    def s_abs(t):
        i = int(np.searchsorted(at, t, side="right"))
        return int(total[i - 1]) if i else int(before[0])

    def r_abs(t):
        return int(np.searchsorted(stamps, t, side="right"))

    # the records are the window's own, so R(w0) = 0 wherever the two
    # processes read one clock: a stamp ahead of w0 counts against that
    s0 = s_abs(w0)
    inside = (at > w0) & (at <= w1)
    sent, read = s_abs(w1) - s0, r_abs(w1)
    # R - S is largest just before a step of S, and at the window's end
    one_pass = int(step[inside].max()) if inside.any() else 0
    ahead = max([read - sent] + list(
        np.searchsorted(stamps, at[inside], side="left")
        - (before[inside] - s0)))
    if ahead > one_pass:
        _say(phase="delivery_inconsistent", read_ahead_of_sent=int(ahead),
             largest_pass_tokens=one_pass, sent=sent, read=read,
             passes=int(inside.sum()))
        return None
    arrived = stamps[stamps <= w1]
    area = (float((step[inside] * (w1 - at[inside])).sum())
            - float((w1 - np.maximum(arrived, w0)).sum()))
    tenths = [w0 + seconds * k / TENTHS for k in range(1, TENTHS + 1)]
    lane = (sum(int(s["attrs"].get("tokens") or 0) for s in sp.named(
                spans, "serving/decode_step", (w0, w1)))
            + sum(1 for s in sp.named(spans, "serving/prefill_compute",
                                      (w0, w1))
                  if "error" not in s["attrs"]))
    _say(phase="delivery", seconds=seconds, passes=int(inside.sum()),
         sent=sent, read=read, sent_minus_read_at_end=sent - read,
         sent_by_tenth=[s_abs(t) - s0 for t in tenths],
         read_by_tenth=[r_abs(t) for t in tenths],
         lane_tokens=lane, largest_pass_tokens=one_pass,
         area_token_s=area, sent_before_window=s0,
         unsent_bytes_max=max(int(p["attrs"].get("unsent_bytes") or 0)
                              for p in passes))
    return {"sent": sent, "read": read, "area_token_s": area,
            "seconds": seconds}
