"""The one general traffic generator.  A traffic mix is a data file
(`benchmark/traffic/<mix>.json`); everything a mix can ask for is here.

Every seed offers the SAME work in another order: lengths and arrival gaps
are the n quantiles of their distributions, shuffled by the seed.  Two
seeds therefore differ as two days of the same traffic differ, not as two
different loads, and a run's numbers do not swing with the draw.

The callers run in ONE child process that never touches the chip
(`Generator`): a serving driver starts it first, hands it the endpoint, the
model's name, the requests and the loop's parameters over a pipe, and gets
the `Record`s back when the loop ends, stamped on `time.monotonic()`, which
is one clock for every process of the machine.
"""

import math
import os
import pickle
import resource
import statistics
import struct
import subprocess
import sys
import threading
import time
import traceback

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


def _open_loop(client_factory, model, requests, dues, drain_s):
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


def _closed_loop(client_factory, model, requests, clients, seconds):
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


# ---------------------------------------------------------------------------
# The callers' own process.  The threads that call
# `ServingClient.infer_stream` and stamp every token in Python do not share
# an interpreter with the server under test: in one process the decode
# lane's launch read 20 ms a dispatch where one thread alone drives it in
# 0.9 (PERF.md section 2, PR 34), and two thirds of the threads in that
# queue were the benchmark's.  CLOCK_MONOTONIC is one clock for every
# process of a Linux machine, so the child's stamps stay on the clock of
# the program's spans and of `tracewin`; each job's handshake checks it.
# ---------------------------------------------------------------------------

_CHILD = ("import sys; sys.path.insert(0, sys.argv[1]); "
          "from benchmark import loadgen; loadgen.child_main()")
_LOOPS = {"open": _open_loop, "closed": _closed_loop}


class Ticker(object):
    """A thread that wakes every `INTERVAL_S` and keeps the longest time
    between two wakings, with the moment it ended (time.monotonic()).
    `run.MemoryWatch` keeps the same two numbers in the server's process,
    so a run whose window lost seconds says whether BOTH processes stood
    still then (the machine) or the server's alone."""

    INTERVAL_S = 0.25

    def __init__(self):
        self.wakings, self.longest_gap_s, self.longest_gap_end = 0, 0.0, None
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._loop, daemon=True)

    def _loop(self):
        last = time.monotonic()
        while not self._stop.wait(self.INTERVAL_S):
            now = time.monotonic()
            self.wakings += 1
            if now - last > self.longest_gap_s:
                self.longest_gap_s, self.longest_gap_end = now - last, now
            last = now

    def __enter__(self):
        self._thread.start()
        return self

    def __exit__(self, *exc):
        self._stop.set()
        self._thread.join()


class GeneratorDied(RuntimeError):
    """The generator's process ended, or answered out of turn: an error of
    the run, never an empty window."""


def _send(f, obj):
    data = pickle.dumps(obj, pickle.HIGHEST_PROTOCOL)
    f.write(struct.pack("<Q", len(data)))
    f.write(data)
    f.flush()


def _recv(f):
    head = f.read(8)
    if len(head) < 8:
        raise EOFError("the pipe closed")
    n, = struct.unpack("<Q", head)
    data = f.read(n)
    if len(data) < n:
        raise EOFError("the pipe closed inside a message")
    return pickle.loads(data)       # written by this program's own child


def _jax_backends():
    """Names of the jax backends this process has initialised (none, in a
    generator that only speaks the wire)."""
    bridge = sys.modules.get("jax._src.xla_bridge")
    return sorted(getattr(bridge, "_backends", None) or ())


def _die_with_the_parent():
    """A run that is killed leaves no generator behind: the kernel sends
    this process SIGKILL when the thread that started it ends (Linux's
    PR_SET_PDEATHSIG = 1).  Without it the child would still end, at the
    close of its pipe, but only once the loop it is in has run out."""
    import ctypes
    import signal
    try:
        ctypes.CDLL(None, use_errno=True).prctl(1, int(signal.SIGKILL))
    except (OSError, AttributeError):
        return
    if os.getppid() == 1:            # it ended before the call
        os._exit(1)


def child_main():
    """The child's whole life: say hello once the client's module is
    imported, then for each job answer "ready", wait for "go", run the
    loop and send the records back; end at "close" or when the pipe does.
    Frames travel over the file descriptors the process was started with
    as 0 and 1; what anything here prints goes to standard error."""
    inp = os.fdopen(os.dup(0), "rb")
    out = os.fdopen(os.dup(1), "wb")
    os.dup2(2, 1)
    _die_with_the_parent()
    from paddle_tpu.serving.server import ServingClient
    _send(out, {"hello": os.getpid(),
                "jax_platforms": os.environ.get("JAX_PLATFORMS")})
    while True:
        try:
            job = _recv(inp)
        except EOFError:
            return
        if job["cmd"] == "close":
            return
        try:
            endpoint = job["endpoint"]
            factory = lambda: ServingClient(endpoint)       # noqa: E731
            _send(out, {"ready": time.monotonic()})
            if _recv(inp)["cmd"] != "go":
                return
            ru0, w0 = resource.getrusage(resource.RUSAGE_SELF), time.time()
            with Ticker() as tick:
                t0, recs = _LOOPS[job["loop"]](factory, job["model"],
                                               job["requests"], *job["args"])
            ru1 = resource.getrusage(resource.RUSAGE_SELF)
            _send(out, {"t0": t0, "records": recs, "stats": {
                "cpu_user_s": ru1.ru_utime - ru0.ru_utime,
                "cpu_sys_s": ru1.ru_stime - ru0.ru_stime,
                "wall_s": time.time() - w0, "pid": os.getpid(),
                "longest_gap_s": tick.longest_gap_s,
                "longest_gap_at_s": (tick.longest_gap_end - t0
                                     if tick.longest_gap_end else None),
                "threads_left": threading.active_count(),
                "jax_platforms": os.environ.get("JAX_PLATFORMS"),
                "jax_backends": _jax_backends()}})
        except Exception:            # the parent raises it; the child lives
            _send(out, {"error": traceback.format_exc()})


class Generator(object):
    """The handle a serving driver holds on the generator's process.  Made
    as the driver's FIRST act, so that the child's imports (the client's
    module pulls in jax, ~10 s) run beside the weights' drawing and the
    model's loading; `serve(endpoint)` once the server listens; `close()`
    in the driver's `finally`.  The child is pinned to the CPU backend (a
    chip belongs to one process) and never touches jax."""

    def __init__(self):
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        self.endpoint = None
        self.stats = None            # of the last job
        self._hello = None
        self._proc = subprocess.Popen(
            [sys.executable, "-c", _CHILD, root], stdin=subprocess.PIPE,
            stdout=subprocess.PIPE, env=env, cwd=root)

    def serve(self, endpoint):
        self.endpoint = endpoint
        return self

    def _read(self):
        try:
            msg = _recv(self._proc.stdout)
        except (EOFError, OSError) as e:
            raise GeneratorDied(
                "the load generator's process is gone (exit code %r): %s"
                % (self._proc.wait(timeout=30), e))
        if "error" in msg:
            raise GeneratorDied("the load generator failed:\n"
                                + msg["error"])
        return msg

    def _write(self, msg):
        try:
            _send(self._proc.stdin, msg)
        except OSError as e:
            raise GeneratorDied(
                "the load generator's process is gone (exit code %r): %s"
                % (self._proc.wait(timeout=30), e))

    def hello(self):
        """The child's first words (waits for its imports)."""
        if self._hello is None:
            self._hello = self._read()
            if self._hello.get("jax_platforms") != "cpu":
                raise GeneratorDied("the load generator is not pinned to "
                                    "the CPU: %r" % (self._hello,))
        return self._hello

    def run(self, loop, model, requests, *args):
        """One job: (t0, records), the child's clock held to this one."""
        if self.endpoint is None:
            raise RuntimeError("Generator.serve(endpoint) comes first")
        self.hello()
        before = time.monotonic()
        self._write({"cmd": "job", "loop": loop, "endpoint": self.endpoint,
                     "model": model, "requests": requests, "args": args})
        theirs = self._read()["ready"]
        after = time.monotonic()
        if not before <= theirs <= after:
            raise GeneratorDied(
                "the generator's monotonic clock reads %r between this "
                "process's %r and %r: not one clock" % (theirs, before,
                                                        after))
        self._write({"cmd": "go"})
        done = self._read()
        self.stats = dict(done["stats"], clock_round_trip_ms=(
            after - before) * 1e3)
        if "tpu" in self.stats["jax_backends"]:
            raise GeneratorDied("the load generator initialised a TPU "
                                "backend: %r" % (self.stats,))
        return done["t0"], done["records"]

    def close(self):
        """Ask the child to end, wait, and kill what does not."""
        proc = self._proc
        if proc.poll() is None:
            try:
                _send(proc.stdin, {"cmd": "close"})
            except OSError:
                pass
        for f in (proc.stdin, proc.stdout):
            try:
                f.close()
            except OSError:
                pass
        try:
            proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()
        return proc.returncode


def run_open_loop(gen, model, requests, dues, drain_s):
    """`_open_loop` in the generator's process `gen`."""
    return gen.run("open", model, requests, dues, drain_s)


def run_closed_loop(gen, model, requests, clients, seconds):
    """`_closed_loop` in the generator's process `gen`."""
    return gen.run("closed", model, requests, clients, seconds)
