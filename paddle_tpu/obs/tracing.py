"""Span tracing over a fixed-size ring buffer (OBSERVABILITY.md).

The stack has five performance-critical async layers (reader prefetch ->
dispatch pipeline -> serving lanes -> micro-batcher -> compile cache) and
until now no way to say where one request's or one train step's time
went.  This module is the shared answer: a thread-safe ``Span`` record +
``trace()`` context manager writing completed spans into a bounded ring
(``FLAGS.trace_buffer_events``), cheap enough to leave on in production
(<3% on the bench smoke lanes — BENCH_r09.json pins the delta).

Design constraints, in order:

* the hot path NEVER blocks and NEVER raises: span append is one
  ``deque.append`` on a maxlen deque (GIL-atomic; old spans fall off the
  far end — overflow is silent by design and counted);
* disabled tracing is one module-global bool test: ``trace()`` returns a
  shared no-op context manager, no allocation;
* spans are plain data (name, trace_id, kind, parent, start, duration,
  small attr dict), wire-encodable as dicts so the serving ``trace`` RPC
  verb can ship them to ``tools/trace_top.py`` unchanged, and
  chrome-trace convertible so ``profiler.export_chrome_tracing`` can
  merge them with the jax device timeline;
* ONE clock: a span's start (``t0``) and duration are both read from
  ``time.monotonic()``, the clock the benchmark anchors its device
  trace to.  The wall-clock ``ts`` the chrome/JSONL consumers want is
  ``t0`` plus one process-wide offset, read once when this module is
  imported; no span takes a second ``time.time()`` reading, so a step
  of the wall clock moves no span against another or against the
  device events.

Trace ids: every serving request gets one minted at admission (or
carries one in on the wire ``"trace_id"`` field, echoed in the reply);
training spans carry a ``step`` attr instead.  A trace id groups the
request's stage spans (queue_wait / coalesce / lane_wait / compute /
scatter) into the tree ``trace_top`` prints; the stages are stamped from
contiguous timestamps, so they sum to the root span by construction.

Nesting: every thread keeps a stack of its open spans.  A span opened
(``trace()``) or stamped (``stamp()``) inside another records the
enclosing span's name as ``parent`` and takes over its ``trace_id`` and
the attrs of ``_INHERITED`` (``step``, ``round``) unless it sets its
own.  Code that stamps a span by hand AFTER the region it covers
declares the region with ``under()``, so what runs inside still finds
its parent.
"""

import collections
import contextlib
import random
import threading
import time

__all__ = ["Span", "trace", "stamp", "under", "inherited", "new_trace_id",
           "enabled", "set_enabled", "configure", "recent_spans",
           "spans_for_trace", "clear", "stats", "add_span", "chrome_events"]

_lock = threading.Lock()           # guards reconfiguration only
_ring = collections.deque(maxlen=65536)
_enabled = True
_spans_total = 0                   # lifetime appends (overflow = total - len)
_rng = random.Random()
_configured = False

# wall = monotonic + _WALL_OFFSET for every span of this process
_WALL_OFFSET = time.time() - time.monotonic()
# attrs a span passes down to the spans opened inside it
_INHERITED = ("step", "round")


class _Stack(threading.local):
    """This thread's open spans, innermost last: (name, trace_id,
    {inherited attr: value})."""

    def __init__(self):
        self.frames = []


_tls = _Stack()

# one listener hook: the MetricsRegistry aggregates train/serving span
# totals without the emitters knowing about metrics at all
_on_span = None


class Span(object):
    """One completed timed region.  ``t0`` is its start on
    ``time.monotonic()`` and ``dur_ms`` its duration on the same clock;
    ``ts`` (wall-clock epoch seconds, chrome-trace compatible) is
    derived from ``t0``; ``parent`` the name of the enclosing span;
    ``attrs`` a SMALL dict of wire-encodable values (str/int/float)."""

    __slots__ = ("name", "kind", "trace_id", "t0", "dur_ms", "attrs",
                 "thread", "parent")

    def __init__(self, name, kind="", trace_id=None, t0=None, dur_ms=0.0,
                 attrs=None, thread=None, parent=None):
        self.name = name
        self.kind = kind
        self.trace_id = trace_id
        self.t0 = time.monotonic() if t0 is None else t0
        self.dur_ms = dur_ms
        self.attrs = attrs or {}
        self.thread = threading.get_ident() if thread is None else thread
        self.parent = parent

    @property
    def ts(self):
        return self.t0 + _WALL_OFFSET

    def to_dict(self):
        d = {"name": self.name, "kind": self.kind, "ts": self.ts,
             "t0": self.t0, "dur_ms": round(self.dur_ms, 4)}
        if self.trace_id is not None:
            d["trace_id"] = self.trace_id
        if self.parent is not None:
            d["parent"] = self.parent
        if self.attrs:
            d["attrs"] = {str(k): (v if isinstance(v, (int, float, bool))
                                   else str(v))
                          for k, v in self.attrs.items()}
        return d

    def __repr__(self):
        return "Span(%r, %.2fms, trace=%s, %s)" % (
            self.name, self.dur_ms, self.trace_id, self.attrs)


def new_trace_id():
    """16 hex chars, random.  Cheap (no uuid machinery) and long enough
    that a collision inside one ring buffer's lifetime is negligible."""
    return "%016x" % _rng.getrandbits(64)


def _flag(name, default):
    """Read a flag, tolerating a half-initialized flag registry (the
    on_change hooks can fire while flags.py itself is importing)."""
    try:
        from ..flags import FLAGS
        return getattr(FLAGS, name)
    except Exception:
        return default


def _ensure_configured():
    """Lazy first-use sync with FLAGS (flags may be set before this
    module is ever imported; on_change hooks keep us in sync after)."""
    global _configured
    if _configured:
        return
    with _lock:
        if _configured:
            return
        _apply(_flag("trace", _enabled),
               _flag("trace_buffer_events", _ring.maxlen))
        _configured = True


def _apply(enabled_, capacity):
    global _enabled, _ring
    _enabled = bool(enabled_)
    capacity = max(int(capacity), 1)
    if capacity != _ring.maxlen:
        _ring = collections.deque(_ring, maxlen=capacity)


def configure(enabled=None, capacity=None):
    """Reconfigure the tracer (flags on_change hooks route here)."""
    global _configured
    with _lock:
        _apply(_flag("trace", _enabled) if enabled is None else enabled,
               _flag("trace_buffer_events", _ring.maxlen)
               if capacity is None else capacity)
        _configured = True


def enabled():
    _ensure_configured()
    return _enabled


def set_enabled(on):
    global _enabled, _configured
    _enabled = bool(on)
    _configured = True


def set_span_listener(fn):
    """Install the single span listener (MetricsRegistry aggregation);
    None removes it.  Listener exceptions are swallowed — telemetry must
    never take down the traffic it observes."""
    global _on_span
    _on_span = fn


def add_span(span):
    """Append one completed Span.  The hot-path primitive: instrumented
    code that stamps its own timestamps (the batcher's contiguous stage
    spans) builds Spans directly and lands them here."""
    global _spans_total
    _ring.append(span)
    _spans_total += 1
    if _on_span is not None:
        try:
            _on_span(span)
        except Exception:
            pass


class _NullCtx(object):
    """Shared no-op context manager: the disabled-tracing fast path."""
    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *a):
        return False


_NULL = _NullCtx()


def _adopt(span):
    """Fill what `span` leaves open from the innermost open span of this
    thread: parent, trace_id, the inherited attrs."""
    frames = _tls.frames
    if frames:
        name, trace_id, passed = frames[-1]
        if span.parent is None:
            span.parent = name
        if span.trace_id is None:
            span.trace_id = trace_id
        for k, v in passed.items():
            span.attrs.setdefault(k, v)


def _push(name, trace_id, attrs):
    frames = _tls.frames
    passed = {}
    if frames:
        passed.update(frames[-1][2])
        if trace_id is None:
            trace_id = frames[-1][1]
    for k in _INHERITED:
        if k in attrs:
            passed[k] = attrs[k]
    frames.append((name, trace_id, passed))


class _LiveSpan(object):
    """Context manager for one in-progress span; ``__exit__`` stamps the
    duration and lands it in the ring.  An exception inside the region
    still records the span (with ``error`` attr) and propagates."""

    __slots__ = ("_span",)

    def __init__(self, span):
        self._span = span

    def __enter__(self):
        s = self._span
        _adopt(s)
        _push(s.name, s.trace_id, s.attrs)
        s.t0 = time.monotonic()
        return s

    def __exit__(self, exc_type, exc, tb):
        s = self._span
        s.dur_ms = (time.monotonic() - s.t0) * 1e3
        _tls.frames.pop()
        if exc_type is not None:
            s.attrs = dict(s.attrs, error=exc_type.__name__)
        add_span(s)
        return False


class _Under(object):
    """Context manager of ``under()``: an open span on the stack, with
    nothing emitted."""

    __slots__ = ("_frame",)

    def __init__(self, *frame):
        self._frame = frame

    def __enter__(self):
        _push(*self._frame)
        return None

    def __exit__(self, *a):
        _tls.frames.pop()
        return False


def trace(name, kind="", trace_id=None, parent=None, **attrs):
    """``with trace("serving/compute", trace_id=tid, step=3): ...`` —
    the span API everything instruments through.  Returns a no-op
    context when tracing is disabled (one bool test, no allocation).
    `parent` names the enclosing span where that one is stamped by hand
    and is not on this thread's stack."""
    _ensure_configured()
    if not _enabled:
        return _NULL
    return _LiveSpan(Span(name, kind=kind, trace_id=trace_id,
                          attrs=attrs, parent=parent))


def under(name, trace_id=None, **attrs):
    """``with under("serving/decode_step", round=n): ...`` — declare the
    region a hand-stamped span WILL cover (it is stamped after the
    region, when its counts are known), so spans opened inside find
    their parent and inherit `trace_id` / `step` / `round`.  Emits
    nothing; a no-op context when tracing is disabled."""
    _ensure_configured()
    if not _enabled:
        return _NULL
    return _Under(name, trace_id, attrs)


def stamp(name, t0, t1, kind="", trace_id=None, parent=None, **attrs):
    """Land one span from two ``time.monotonic()`` stamps the caller
    took itself (contiguous stage spans that tile their root exactly).
    Callers take their stamps under ``if enabled():``, so a disabled
    tracer costs them one bool test and no clock call."""
    if not _enabled:
        return
    span = Span(name, kind=kind, trace_id=trace_id, t0=t0,
                dur_ms=max(t1 - t0, 0.0) * 1e3, attrs=attrs,
                parent=parent)
    _adopt(span)
    add_span(span)


def inherited(key, default=None):
    """The value of attr `key` (``step`` / ``round``) the innermost open
    span of this thread passes down, or `default`."""
    frames = _tls.frames
    return frames[-1][2].get(key, default) if frames else default


def clear():
    global _spans_total
    with _lock:
        _ring.clear()
        _spans_total = 0


def stats():
    """Ring statistics for the metrics surface."""
    return {"enabled": _enabled, "capacity": _ring.maxlen or 0,
            "buffered": len(_ring), "spans_total": _spans_total,
            "dropped": max(_spans_total - len(_ring), 0)}


def recent_spans(limit=None, kind=None, name=None):
    """Most-recent-last list of span dicts (wire-encodable).  Snapshot
    is GIL-consistent; concurrent appends during iteration are fine."""
    spans = list(_ring)
    if kind:
        spans = [s for s in spans if s.kind == kind]
    if name:
        spans = [s for s in spans if s.name == name]
    if limit is not None and len(spans) > limit:
        spans = spans[-int(limit):]
    return [s.to_dict() for s in spans]


def spans_for_trace(trace_id):
    """Every buffered span of one trace, oldest first — the span tree a
    reply-visible trace_id resolves to."""
    return [s.to_dict() for s in list(_ring) if s.trace_id == trace_id]


def chrome_events(spans=None, pid=None):
    """Convert span dicts to chrome-trace ``X`` events so they merge
    into the jax device timeline (profiler.export_chrome_tracing).
    One synthetic thread row per span kind (serving / train / obs)."""
    import os
    if spans is None:
        spans = recent_spans()
    pid = os.getpid() if pid is None else pid
    out = [{"ph": "M", "pid": pid, "name": "process_name",
            "args": {"name": "paddle_tpu obs spans"}}]
    tids = {}
    for s in spans:
        kind = s.get("kind") or "obs"
        tid = tids.get(kind)
        if tid is None:
            tid = tids[kind] = len(tids) + 1
            out.append({"ph": "M", "pid": pid, "tid": tid,
                        "name": "thread_name",
                        "args": {"name": "obs:%s" % kind}})
        args = dict(s.get("attrs") or {})
        if s.get("trace_id"):
            args["trace_id"] = s["trace_id"]
        out.append({"ph": "X", "pid": pid, "tid": tid,
                    "name": s["name"], "ts": s["ts"] * 1e6,
                    "dur": s["dur_ms"] * 1e3, "args": args})
    return out
