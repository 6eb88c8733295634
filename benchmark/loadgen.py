"""The one general traffic generator.  A traffic mix is a data file
(`benchmark/traffic/<mix>.json`); everything a mix can ask for is here.

Every seed offers the SAME work in another order: lengths and arrival gaps
are the n quantiles of their distributions, shuffled by the seed.  Two
seeds therefore differ as two days of the same traffic differ, not as two
different loads, and a run's numbers do not swing with the draw.
"""

import math
import statistics
import threading
import time

import numpy as np

_NORMAL = statistics.NormalDist()


def quantile_values(dist, n):
    """The n mid-quantiles ((i + 0.5) / n) of `dist`, as integers, sorted.

    dist kinds: {"kind": "lognormal", "median": m, "sigma": s},
    {"kind": "uniform"}, {"kind": "fixed", "value": v}; all clipped to
    [min, max] where given."""
    kind = dist["kind"]
    ps = [(i + 0.5) / n for i in range(n)]
    if kind == "lognormal":
        mu, sigma = math.log(dist["median"]), float(dist["sigma"])
        vals = [math.exp(mu + sigma * _NORMAL.inv_cdf(p)) for p in ps]
    elif kind == "uniform":
        lo, hi = float(dist["min"]), float(dist["max"])
        vals = [lo + (hi - lo) * p for p in ps]
    elif kind == "fixed":
        vals = [float(dist["value"])] * n
    else:
        raise ValueError("unknown distribution kind %r" % (kind,))
    lo, hi = dist.get("min"), dist.get("max")
    out = []
    for v in vals:
        if lo is not None:
            v = max(v, lo)
        if hi is not None:
            v = min(v, hi)
        out.append(int(round(v)))
    return out


def exponential_gaps(rate, n):
    """The n mid-quantiles of the exponential inter-arrival distribution of
    a Poisson process of `rate` per second, sorted."""
    return [-math.log(1.0 - (i + 0.5) / n) / rate for i in range(n)]


def _rng(seed, stream):
    # --seed may exceed 2**31; numpy takes any non-negative integer
    return np.random.default_rng([int(seed), int(stream)])


def make_requests(traffic, seed, n, vocab_size):
    """n requests {"prompt": int32 array, "max_new": int} from the mix's
    length distributions, in the seed's order.  Token 0 is eos in the
    program's decode artifacts and is kept out of the prompts."""
    rng = _rng(seed, 1)
    plens = quantile_values(traffic["prompt_tokens"], n)
    olens = quantile_values(traffic["output_tokens"], n)
    rng.shuffle(plens)
    rng.shuffle(olens)
    return [{"prompt": rng.integers(1, vocab_size, size=p, dtype=np.int32),
             "max_new": int(o)} for p, o in zip(plens, olens)]


def due_times(traffic, seed, seconds):
    """Open loop: due times (seconds from the window's start) of
    round(rate * seconds) requests; the gaps are the exponential's
    quantiles in the seed's order, so the last request is due at about
    `seconds` whatever the seed."""
    rate = float(traffic["rate_per_s"])
    n = max(int(round(rate * seconds)), 1)
    gaps = exponential_gaps(rate, n)
    _rng(seed, 2).shuffle(gaps)
    t, out = 0.0, []
    for g in gaps:
        out.append(t)
        t += g
    return out


class Record(object):
    """What the generator saw of one request (times: time.monotonic())."""

    __slots__ = ("index", "due", "sent", "token_times", "tokens", "done",
                 "info", "error", "cancelled", "prompt_len", "max_new")

    def __init__(self, index, due, req):
        self.index = index
        self.due = due
        self.sent = None
        self.token_times = []      # one entry per TOKEN (a frame's share it)
        self.tokens = []
        self.done = None
        self.info = None
        self.error = None
        self.cancelled = False
        self.prompt_len = int(len(req["prompt"]))
        self.max_new = int(req["max_new"])

    def ok(self, eos_id, max_seq_len):
        """Ended with its terminal frame, and the frame agrees with what
        arrived: all the tokens asked for, or fewer only because eos came
        or the context was full."""
        i = self.info
        if (self.error is not None or self.cancelled or not i
                or not i.get("done")
                or i.get("finish_reason") not in ("length", "eos")
                or i.get("new_tokens") != len(self.tokens)
                or not self.tokens):
            return False
        return (len(self.tokens) == self.max_new
                or self.tokens[-1] == eos_id
                or self.prompt_len + len(self.tokens) >= max_seq_len - 1)


def _stream(client_factory, model, req, rec, stop):
    """One request through `ServingClient.infer_stream`, stamped."""
    client = client_factory()
    try:
        rec.sent = time.monotonic()
        it = client.infer_stream(model, req["prompt"],
                                 max_new_tokens=req["max_new"])
        try:
            for delta in it:
                now = time.monotonic()
                rec.tokens.extend(delta)
                rec.token_times.extend([now] * len(delta))
                if stop is not None and stop.is_set():
                    rec.cancelled = True
                    break
        finally:
            it.close()
        rec.info = client.last_stream_info
    except Exception as e:           # the request counts as failed
        rec.error = "%s: %s" % (type(e).__name__, e)
    finally:
        rec.done = time.monotonic()
        client.close()


def run_open_loop(client_factory, model, requests, dues, drain_s):
    """Send request i at t0 + dues[i] whatever the server does (one thread
    per request, started when due), then wait up to `drain_s` after the
    last due time.  Returns (t0, records); a request not done by then keeps
    done=None and counts as failed."""
    recs = [Record(i, d, r) for i, (d, r) in enumerate(zip(dues, requests))]
    threads = []
    t0 = time.monotonic() + 0.05
    for rec, req in zip(recs, requests):
        rec.due = t0 + rec.due
        wait = rec.due - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        th = threading.Thread(target=_stream, daemon=True,
                              args=(client_factory, model, req, rec, None))
        th.start()
        threads.append(th)
    limit = recs[-1].due + drain_s
    for th in threads:
        th.join(timeout=max(limit - time.monotonic(), 0.0))
    for rec, th in zip(recs, threads):
        if th.is_alive():
            rec.done = None
    return t0, recs


def run_closed_loop(client_factory, model, requests, clients, seconds):
    """`clients` callers, each sending its next request when its last one
    ended, for `seconds`; at the end of the window the streams in flight
    are cancelled (closing the iterator drops the connection, which evicts
    the request) and marked so.  Returns (t0, records)."""
    recs, lock, stop = [], threading.Lock(), threading.Event()
    cursor = [0]

    def caller():
        while not stop.is_set():
            with lock:
                i = cursor[0]
                cursor[0] += 1
                req = requests[i % len(requests)]
                rec = Record(i, time.monotonic(), req)
                recs.append(rec)
            _stream(client_factory, model, req, rec, stop)

    threads = [threading.Thread(target=caller, daemon=True)
               for _ in range(clients)]
    t0 = time.monotonic()
    for th in threads:
        th.start()
    time.sleep(max(t0 + seconds - time.monotonic(), 0.0))
    stop.set()
    for th in threads:
        th.join(timeout=60.0)
    for rec in recs:
        if rec.done is None or rec.done > t0 + seconds:
            rec.cancelled = True
    return t0, sorted(recs, key=lambda r: r.index)
