"""Cross-request dynamic micro-batcher with per-replica dispatch lanes.

The serving front's core mechanism: many callers each submit a small
(often batch-1) request; TPU executables want the biggest batch bucket
they were compiled for (the MLPerf TPU-pod study's lesson — batch
geometry IS the utilization lever).  The batcher closes that gap by
coalescing waiting requests into one padded bucket dispatch, and — the
multi-chip half — fans the coalesced groups out across N device-placed
model replicas so all chips on the host serve one model name:

  * bounded queue per model (admission control: a submit past
    `max_queue` is shed with `ServerOverloaded`, never parked on an
    unbounded backlog — shed-not-hang). Requests carry a `priority`
    class: under overload the queue sheds lowest-priority-first (an
    arriving request evicts the lowest strictly-lower-priority queued
    request rather than being refused), and the ServerOverloaded a shed
    request receives names the priority class that was dropped;
  * a router thread takes the head request, greedily pulls compatible
    queued requests until the largest bucket is full or a
    `FLAGS.serving_batch_deadline_ms` window expires, then hands the
    group to the LEAST-LOADED replica lane (fewest in-flight batches,
    then shortest lane queue) — the replica-per-accelerator pattern of
    the Clipper/TF-Serving lineage, with the reference
    ParallelExecutor's shape (one program, N device-resident copies,
    work fanned out by the runtime) applied to serving;
  * each lane is a bounded deque in front of one replica predictor plus
    its own dispatch worker(s), so two replicas can be mid-`dispatch`
    concurrently — the PR 4 pipeline lesson (keep the device busy,
    drain asynchronously) turned into cross-chip parallelism. When
    every lane is full the router holds the group (sticky back-
    pressure): the admission queue fills and new submits shed, so
    overload still sheds at the front instead of hiding in per-lane
    backlogs;
  * batch-major feeds (the program-var -1 leading-dim markers the AOT
    meta records and the live Predictor exposes the same way) are
    concatenated; fixed-shape side feeds must be byte-identical to
    coalesce and ride through whole;
  * the underlying predictor pads the merged batch up to its bucket and
    un-pads batch-major fetches (that parity is the predictor's existing
    contract and holds identically on every replica — replies are
    bit-exact vs a direct Predictor.run regardless of which lane served
    them); the lane worker scatters per-request row slices back to each
    caller's Future.

Compatibility grouping: requests only coalesce when their feed names,
trailing shapes, dtypes, and side-feed bytes agree — everything else
dispatches as its own group, correct but uncoalesced.

Chaos: `set_dispatch_delay(secs)` (or env
`PADDLE_TPU_SERVING_CHAOS="dispatch_delay=<secs>"`) injects a slow-worker
stall inside every lane's dispatch — the overload scenarios in
tools/chaos.py and tests/test_serving.py drive admission control with
it, and tools/bench_serving.py reuses it as the deterministic per-
dispatch device-cost stand-in for the replica-scaling lanes.
"""

import binascii
import collections
import contextlib
import os
import queue as queue_mod
import threading
import time
from concurrent.futures import Future

import numpy as np

from ..flags import FLAGS
from ..obs import events as obs_events
from ..obs import tracing as obs_tracing
from ..parallel.mesh import MeshMemberLost

__all__ = ["DynamicBatcher", "DecodeBatcher", "DecodeStream",
           "ServerOverloaded", "DeadlineExceeded", "BatcherClosed",
           "set_dispatch_delay", "set_draft_delay", "set_host_delay"]

_CHAOS_ENV = "PADDLE_TPU_SERVING_CHAOS"


class ServerOverloaded(RuntimeError):
    """Admission control shed: the model's request queue is full.
    Explicit and immediate — the client can back off and retry
    (utils/retry.py jitter) instead of waiting on a hidden backlog.
    `priority` names the class of the request that was shed (the
    arriving one, or a lower-priority queued request it evicted)."""

    def __init__(self, message, priority=None):
        super().__init__(message)
        self.priority = priority


class DeadlineExceeded(TimeoutError):
    """The request's deadline passed before its dispatch completed."""


class BatcherClosed(RuntimeError):
    """Submit on a draining/retired batcher (e.g. mid hot-swap retire)."""


_dispatch_delay = 0.0


def set_dispatch_delay(secs):
    """Chaos hook: every subsequent dispatch sleeps `secs` first —
    the in-process slow-worker fault (0 clears).  The sleep happens in
    the lane worker thread with the GIL released, so concurrent
    replica lanes overlap their stalls — which is also what makes it
    the deterministic stand-in for per-dispatch device time in
    bench_serving's replica-scaling lanes."""
    global _dispatch_delay
    _dispatch_delay = float(secs)


def _chaos_delay(key="dispatch_delay", direct=None):
    if direct is None:
        direct = _dispatch_delay
    if direct:
        return direct
    spec = os.environ.get(_CHAOS_ENV)
    if spec:
        for part in spec.split(","):
            name, _, val = part.partition("=")
            if name.strip() == key:
                try:
                    return float(val)
                except ValueError:
                    pass
    return 0.0


_draft_delay = 0.0


def set_draft_delay(secs):
    """Per-DRAFT-step stand-in cost for speculative decode lanes (the
    companion of set_dispatch_delay, which prices the target/verify
    step): every draft decode step sleeps `secs` first, GIL released.
    bench_serving --draft_cost_ms rides this — with the int8 twin as
    the draft, ~0.3x the target step cost is the honest BENCH_r11
    weight-bytes ratio (0 clears)."""
    global _draft_delay
    _draft_delay = float(secs)


def _draft_chaos_delay():
    return _chaos_delay(key="draft_delay", direct=_draft_delay)


_host_delay = 0.0


def set_host_delay(secs):
    """Per-DISPATCH host-side cost stand-in (SERVING.md "Fused
    multi-step decode"): every decode dispatch sleeps `secs` once
    before launching, GIL released — the deterministic model of the
    host round-trip (Python scheduling + launch + sync) that fused
    decode amortizes.  At N=1 a stream pays host+step per token; at
    fuse_steps=N it pays host once per N tokens — bench_serving
    --host_cost_ms rides this to show the dispatch-amortization win
    at real step costs (0 clears)."""
    global _host_delay
    _host_delay = float(secs)


def _host_chaos_delay():
    return _chaos_delay(key="host_cost", direct=_host_delay)


def _predictor_device_label(predictor):
    from ..inference.predictor import _device_label
    return _device_label(getattr(predictor, "device", None))


def _guarded(fn, model_name_fn, thread_kind):
    """Wrap a batcher thread main: an exception escaping the loop is a
    dead router/lane — a request-eating wedge that used to die silently
    as a daemon thread.  Now it lands a `server_thread_death` event and
    arms the flight recorder (obs/flightrec.py) before re-raising, so
    the post-mortem bundle holds the stack that killed it."""
    def _run(*args):
        try:
            fn(*args)
        except BaseException as e:
            name = threading.current_thread().name
            obs_events.emit("server_thread_death",
                            model=model_name_fn(), thread=name,
                            thread_kind=thread_kind,
                            error="%s: %s" % (type(e).__name__, e))
            from ..obs import flightrec
            flightrec.trigger("thread_death", thread=name,
                              thread_kind=thread_kind,
                              model=model_name_fn() or "",
                              error="%s: %s" % (type(e).__name__, e))
            raise
    return _run


class _Request:
    __slots__ = ("feeds", "batch", "future", "group_key", "enqueued",
                 "deadline", "priority", "trace_id", "t_taken",
                 "t_grouped")

    def __init__(self, feeds, batch, group_key, deadline, priority,
                 trace_id=None):
        self.feeds = feeds
        self.batch = batch
        self.group_key = group_key
        self.deadline = deadline
        self.priority = priority
        self.future = Future()
        self.enqueued = time.monotonic()
        # observability (OBSERVABILITY.md): the request's trace id plus
        # the monotonic stamps the stage spans are cut from — contiguous
        # by construction, so queue_wait + coalesce + lane_wait +
        # dispatch + compute + scatter sums to the root span exactly
        self.trace_id = trace_id or obs_tracing.new_trace_id()
        self.t_taken = None     # router popped/pulled it off the queue
        self.t_grouped = None   # its dispatch group closed coalescing


class _Lane:
    """One replica's execution lane: a bounded ready deque feeding this
    replica's dispatch worker(s), plus the load counters the router's
    least-loaded choice reads (in-flight batches first, then queue
    length)."""

    __slots__ = ("index", "predictor", "device", "ready", "inflight",
                 "batches", "rows", "last_t", "dead", "tp",
                 "disp_ewma")

    def __init__(self, index, predictor):
        self.index = index
        self.predictor = predictor
        self.device = _predictor_device_label(predictor)
        self.ready = collections.deque()
        self.inflight = 0   # groups a worker is currently dispatching
        self.batches = 0    # micro-batches this replica executed
        self.rows = 0       # real rows it served
        self.last_t = None  # monotonic end of this lane's last dispatch
        # tensor-parallel lane (SERVING.md "Tensor-parallel compute"):
        # the replica runs the partitioned program, so dispatch time
        # tracks per-member (~1/mesh) HBM traffic, not the whole model
        self.tp = bool(getattr(predictor, "tp_active", False))
        self.disp_ewma = None  # EWMA seconds per dispatch (run only)
        # set to the error string when a mesh member died under this
        # lane (SERVING.md "Mesh replicas"): the router skips it, its
        # workers exit, sibling lanes keep serving
        self.dead = None

    def load(self):
        return (self.inflight, len(self.ready), self.index)

    @property
    def mesh(self):
        """Members behind this lane: 1 for a plain device, N for a
        mesh-group replica ('a+b' device label)."""
        return self.device.count("+") + 1 if self.device else 1


class DynamicBatcher:
    """Micro-batcher over one or more replica predictors (each a
    `Predictor` or `AotPredictor` — anything with `.run(dict)->list`
    plus the serving introspection quartet: `batch_buckets`,
    `feed_specs`, `batched_feed_names`, `fetch_batched_flags`).

    `replicas`: optional list of device-placed predictors sharing one
    model's weights (the registry builds them via `clone_to`); the
    batcher runs one execution lane per replica and routes each
    coalesced group to the least-loaded lane.  Without it, the single
    `predictor` forms the only lane — the pre-multichip behavior."""

    def __init__(self, predictor, max_queue=None, deadline_ms=None,
                 workers=None, metrics=None, max_batch=None,
                 replicas=None, lane_depth=None):
        preds = list(replicas) if replicas else [predictor]
        self.predictor = predictor if predictor is not None else preds[0]
        self.max_queue = int(FLAGS.serving_max_queue
                             if max_queue is None else max_queue)
        self.deadline_s = (FLAGS.serving_batch_deadline_ms
                           if deadline_ms is None else
                           float(deadline_ms)) / 1000.0
        self.lane_depth = max(int(FLAGS.serving_lane_depth
                                  if lane_depth is None else lane_depth),
                              1)
        self.metrics = metrics
        self.buckets = tuple(self.predictor.batch_buckets())
        if max_batch is not None:
            self.max_batch = int(max_batch)
        elif self.buckets:
            self.max_batch = self.buckets[-1]
        else:
            self.max_batch = 64  # unbucketed predictor: a sane coalesce cap
        self._batched_feeds = frozenset(
            self.predictor.batched_feed_names())
        self._fetch_flags = self.predictor.fetch_batched_flags()
        self._cv = threading.Condition()
        self._pending = collections.deque()
        self._lanes = [_Lane(i, p) for i, p in enumerate(preds)]
        self._carrying = False  # router holds a taken-but-unrouted group
        self._closing = False
        self._stopped = False
        if metrics is not None:
            metrics.queue_depth_fn = lambda: len(self._pending)
            metrics.replica_stats_fn = self.replica_stats
        n_workers = max(int(FLAGS.serving_workers if workers is None
                            else workers), 1)
        self._router = threading.Thread(
            target=_guarded(self._route, lambda: self._model_name,
                            "router"),
            daemon=True, name="paddle-tpu-serving-router")
        self._lane_threads = {lane.index: [] for lane in self._lanes}
        self._threads = []
        for lane in self._lanes:
            for i in range(n_workers):
                t = threading.Thread(
                    target=_guarded(self._worker,
                                    lambda: self._model_name, "lane"),
                    args=(lane,), daemon=True,
                    name="paddle-tpu-serving-lane%d-%d"
                         % (lane.index, i))
                self._threads.append(t)
                self._lane_threads[lane.index].append(t)
        self._router.start()
        for t in self._threads:
            t.start()

    @property
    def num_replicas(self):
        return len(self._lanes)

    # ------------------------------------------------------------------
    # submit side (admission control)
    # ------------------------------------------------------------------

    @property
    def _model_name(self):
        return self.metrics.name if self.metrics is not None else None

    def _build_request(self, feeds, deadline, priority, trace_id=None):
        named = {k: np.asarray(v) for k, v in feeds.items()}
        batch = None
        key_parts = []
        for name in sorted(named):
            arr = named[name]
            if name in self._batched_feeds and arr.ndim >= 1:
                b = arr.shape[0]
                if batch is None:
                    batch = b
                elif b != batch:
                    raise ValueError(
                        "inconsistent request batch: feed %r has leading "
                        "dim %d, another batch-major feed has %d"
                        % (name, b, batch))
                key_parts.append((name, arr.shape[1:], str(arr.dtype)))
            else:
                # side feeds must be byte-identical to share a dispatch
                key_parts.append((name, arr.shape, str(arr.dtype),
                                  binascii.crc32(
                                      np.ascontiguousarray(arr).tobytes())))
        if batch is not None and batch > self.max_batch:
            raise ValueError(
                "request batch %d exceeds the largest servable bucket %d "
                "(buckets %s) — split the request"
                % (batch, self.max_batch, self.buckets or "(none)"))
        return _Request(named, batch, tuple(key_parts), deadline,
                        int(priority), trace_id=trace_id)

    def submit(self, feeds, deadline=None, priority=0, trace_id=None):
        """Enqueue one request (dict name->array).  Returns a Future
        resolving to the fetch list (this request's rows only).
        `deadline` is an absolute time.monotonic() instant or None.
        `priority`: larger = more important; under overload the queue
        sheds lowest-priority-first.  Raises ServerOverloaded /
        BatcherClosed / ValueError synchronously — admission decisions
        are immediate.  `trace_id` carries a caller-minted id (the wire
        `"trace_id"` field); one is minted here otherwise, and the
        returned future exposes it (plus the server-measured stage
        timings) as ``future.obs_info`` once resolved."""
        req = self._build_request(feeds, deadline, priority,
                                  trace_id=trace_id)
        evicted = None
        with self._cv:
            if self._closing:
                raise BatcherClosed("model batcher is draining/retired")
            if len(self._pending) >= self.max_queue:
                # priority shed: evict the lowest strictly-lower-priority
                # queued request (earliest such) in favor of this one;
                # with no lower class queued, the arrival itself sheds
                victim = None
                for r in self._pending:
                    if r.priority < req.priority and \
                            (victim is None
                             or r.priority < victim.priority):
                        victim = r
                if victim is None:
                    if self.metrics is not None:
                        self.metrics.note_shed(priority=req.priority)
                    # a shed happens BEFORE lane routing, so no replica
                    # owns it; the lane-occupancy context says whether
                    # the lanes were saturated or just the queue
                    obs_events.emit("shed", model=self._model_name,
                                    priority=req.priority,
                                    trace_id=req.trace_id,
                                    queue=len(self._pending),
                                    inflight=self._inflight_total())
                    raise ServerOverloaded(
                        "request queue full (%d waiting, max_queue=%d) — "
                        "priority-%d request shed; back off and retry"
                        % (len(self._pending), self.max_queue,
                           req.priority),
                        priority=req.priority)
                self._pending.remove(victim)
                evicted = victim
            self._pending.append(req)
            if self.metrics is not None:
                self.metrics.requests.add()
            # notify_all, not notify: the router AND the lane workers
            # share this condition — a single notify could wake a lane
            # worker (predicate false) and leave the router sleeping
            # out its 0.1s poll, which the new queue_wait span exposed
            # as a ~100ms floor on idle-server latency
            self._cv.notify_all()
        req.future.trace_id = req.trace_id
        if evicted is not None:
            if self.metrics is not None:
                self.metrics.note_shed(priority=evicted.priority)
            obs_events.emit("shed", model=self._model_name,
                            priority=evicted.priority,
                            trace_id=evicted.trace_id, evicted=True,
                            by_priority=req.priority,
                            inflight=self._inflight_total())
            if evicted.future.set_running_or_notify_cancel():
                evicted.future.set_exception(ServerOverloaded(
                    "priority-%d request shed from a full queue by a "
                    "priority-%d arrival (lowest-priority-first "
                    "overload policy)"
                    % (evicted.priority, req.priority),
                    priority=evicted.priority))
        return req.future

    def queue_depth(self):
        return len(self._pending)

    def replica_stats(self):
        """Per-replica lane snapshot (device id, in-flight batches,
        lane queue depth, batches/rows executed) — the skew-visibility
        numbers `stats` and serving_top surface.  `mesh` is the member
        count behind the lane (1 = plain device); `dead` carries the
        mesh-member-loss error when the lane died; `tp` marks a
        tensor-parallel lane and `dispatch_ms` its EWMA device time
        per dispatch (None until the first one)."""
        with self._cv:
            return [{"replica": l.index, "device": l.device,
                     "mesh": l.mesh, "dead": l.dead, "tp": l.tp,
                     "dispatch_ms": round(l.disp_ewma * 1000.0, 3)
                     if l.disp_ewma is not None else None,
                     "inflight": l.inflight, "queue": len(l.ready),
                     "batches": l.batches, "rows": l.rows}
                    for l in self._lanes]

    def lane_liveness(self):
        """Thread-level health of this batcher (the `health` RPC verb's
        per-model section): is the router alive, is each lane's worker
        set alive, and how long since each lane last finished a
        dispatch (None = never dispatched yet)."""
        now = time.monotonic()
        with self._cv:
            lanes = []
            for l in self._lanes:
                threads = self._lane_threads.get(l.index, [])
                lanes.append({
                    "replica": l.index, "device": l.device,
                    "mesh": l.mesh, "dead": l.dead,
                    "alive": sum(1 for t in threads if t.is_alive()),
                    "workers": len(threads),
                    "inflight": l.inflight, "queue": len(l.ready),
                    "last_dispatch_age_s":
                        round(now - l.last_t, 3)
                        if l.last_t is not None else None})
            return {"kind": "batch",
                    "router_alive": self._router.is_alive(),
                    "queue_depth": len(self._pending),
                    "closing": self._closing, "lanes": lanes}

    def _inflight_total(self):
        return sum(l.inflight + len(l.ready) for l in self._lanes)

    # ------------------------------------------------------------------
    # coalescing front-end + least-loaded router
    # ------------------------------------------------------------------

    def _bucket_cap(self, total):
        for cap in self.buckets:
            if total <= cap:
                return cap
        return total

    def _take_group(self):
        """Pop the head request plus every compatible queued request up
        to the largest bucket, waiting up to the coalescing deadline for
        stragglers.  Returns None only at shutdown.  Marks the router as
        carrying the group so drain() sees it between queue and lane."""
        with self._cv:
            while not self._pending:
                if self._stopped or self._closing:
                    return None
                self._cv.wait(0.1)
            head = self._pending.popleft()
            # carrying from the moment the head leaves the queue: the
            # coalescing wait below releases the lock, and a drain()
            # that found nothing queued and nothing carried would let
            # close() stop the router with this group in hand
            self._carrying = True
            head.t_taken = time.monotonic()
            group = [head]
            if head.batch is None:
                # no batch-major feed: nothing to coalesce on
                return group
            total = head.batch
            window = time.monotonic() + self.deadline_s
            while total < self.max_batch:
                took = False
                for i, r in enumerate(self._pending):
                    if r.group_key == head.group_key and \
                            total + r.batch <= self.max_batch:
                        del self._pending[i]
                        r.t_taken = time.monotonic()
                        group.append(r)
                        total += r.batch
                        took = True
                        break
                if took:
                    continue
                if self._pending:
                    # only incompatible (or non-fitting) requests wait —
                    # dispatch now rather than head-of-line block them
                    break
                remaining = window - time.monotonic()
                if remaining <= 0 or self._stopped or self._closing:
                    break
                self._cv.wait(min(remaining, 0.05))
            return group

    def _assign(self, group):
        """Hand `group` to the least-loaded LIVE lane: fewest in-flight
        batches, then shortest lane queue, then lowest index.  When
        every lane's queue is at `lane_depth` the router WAITS here
        (sticky back-pressure) — the admission queue upstream fills and
        sheds, rather than any lane queue growing unboundedly.  Lanes
        killed by mesh-member loss are skipped; with EVERY lane dead
        the group fails typed (MeshMemberLost) instead of parking
        forever.  Returns False only on hard stop (group unrouted)."""
        while True:
            with self._cv:
                if self._stopped:
                    self._carrying = False
                    return False
                live = [l for l in self._lanes if l.dead is None]
                if live:
                    lane = min(live, key=_Lane.load)
                    if len(lane.ready) < self.lane_depth:
                        lane.ready.append(group)
                        self._carrying = False
                        self._cv.notify_all()
                        return True
                    self._cv.wait(0.05)
                    continue
                dead_msg = self._lanes[0].dead
                self._carrying = False
                self._cv.notify_all()
            err = MeshMemberLost(
                "every replica lane is dead (%s)" % dead_msg)
            for r in group:
                if r.future.set_running_or_notify_cancel():
                    r.future.set_exception(err)
            if self.metrics is not None:
                self.metrics.errors.add(len(group))
            return True

    def _route(self):
        while True:
            group = self._take_group()
            if group is None:
                return
            t_grouped = time.monotonic()
            for r in group:
                r.t_grouped = t_grouped
            if not self._assign(group):
                # hard stop with a group in hand: fail it explicitly
                for r in group:
                    if r.future.set_running_or_notify_cancel():
                        r.future.set_exception(BatcherClosed(
                            "server shut down before dispatch"))
                    if self.metrics is not None:
                        self.metrics.errors.add()
                return

    # ------------------------------------------------------------------
    # lane dispatch side
    # ------------------------------------------------------------------

    def _merge_feeds(self, group):
        first = group[0]
        if len(group) == 1:
            return dict(first.feeds)
        merged = {}
        for name, arr in first.feeds.items():
            if name in self._batched_feeds and arr.ndim >= 1:
                merged[name] = np.concatenate(
                    [r.feeds[name] for r in group], axis=0)
            else:
                merged[name] = arr  # group key proved byte-equality
        return merged

    def _emit_request_spans(self, r, lane, t_start, t_run, t_run_end,
                            now, n_live, total):
        """Land one request's stage span set in the tracing ring.  The
        stamps are contiguous monotonic instants, so the stages tile the
        root `serving/request` span exactly: a p99 outlier decomposes
        into WHICH stage ate the time (OBSERVABILITY.md)."""
        model = self._model_name
        tid = r.trace_id
        t_taken = r.t_taken if r.t_taken is not None else t_start
        t_grouped = r.t_grouped if r.t_grouped is not None else t_start

        def _mk(name, t0, t1, parent="serving/request", **attrs):
            if model:
                attrs["model"] = model
            obs_tracing.stamp(name, t0, t1, kind="serving", trace_id=tid,
                              parent=parent, **attrs)

        _mk("serving/queue_wait", r.enqueued, t_taken)
        _mk("serving/coalesce", t_taken, t_grouped)
        _mk("serving/lane_wait", t_grouped, t_start, replica=lane.index)
        _mk("serving/dispatch", t_start, t_run, replica=lane.index)
        _mk("serving/compute", t_run, t_run_end, replica=lane.index,
            rows=total, batch_fill=n_live)
        _mk("serving/scatter", t_run_end, now)
        _mk("serving/request", r.enqueued, now, parent=None,
            replica=lane.index, batch=r.batch or 0, batch_fill=n_live,
            priority=r.priority)

    def _scatter(self, group, fetches, total, lane, t_start, t_run,
                 t_run_end):
        flags = self._fetch_flags
        offset = 0
        now = time.monotonic()
        traced = obs_tracing.enabled()
        try:
            slow_ms = float(FLAGS.trace_slow_ms)
        except Exception:
            slow_ms = 0.0
        for r in group:
            outs = []
            for i, a in enumerate(fetches):
                if flags is not None:
                    batched = i < len(flags) and flags[i]
                else:  # pre-marker AOT artifact: shape heuristic
                    batched = a.ndim >= 1 and a.shape[0] == total
                if batched and r.batch is not None:
                    outs.append(a[offset:offset + r.batch])
                else:
                    outs.append(a)
            offset += r.batch or 0
            total_ms = (now - r.enqueued) * 1000.0
            queue_wait_ms = ((r.t_taken if r.t_taken is not None else now)
                             - r.enqueued) * 1000.0
            if traced:
                self._emit_request_spans(r, lane, t_start, t_run,
                                         t_run_end, now, len(group),
                                         total)
            if slow_ms and total_ms >= slow_ms:
                # the slow-request log: findable after the ring
                # wrapped, attributed to the lane that served it so
                # per-replica triage works from the event log alone
                obs_events.emit("slow", model=self._model_name,
                                trace_id=r.trace_id,
                                replica=lane.index, device=lane.device,
                                total_ms=round(total_ms, 3),
                                queue_wait_ms=round(queue_wait_ms, 3),
                                compute_ms=round(
                                    (t_run_end - t_run) * 1e3, 3))
            if not r.future.set_running_or_notify_cancel():
                continue  # caller cancelled while queued
            # server-measured latency attribution, readable by the
            # caller (ServingClient debug replies) without server access
            r.future.obs_info = {
                "trace_id": r.trace_id,
                "queue_wait_ms": round(queue_wait_ms, 3),
                "coalesce_ms": round(
                    ((r.t_grouped or now) -
                     (r.t_taken if r.t_taken is not None else now))
                    * 1e3, 3),
                "lane_wait_ms": round(
                    (t_start - (r.t_grouped or t_start)) * 1e3, 3),
                "compute_ms": round((t_run_end - t_run) * 1e3, 3),
                "server_ms": round(total_ms, 3),
                "batch_fill": len(group),
                "batch_rows": total,
                "replica": lane.index,
            }
            r.future.set_result(outs)
            if self.metrics is not None:
                self.metrics.note_completion(
                    latency_ms=total_ms, queue_wait_ms=queue_wait_ms)

    def _dispatch(self, group, lane):
        t_start = time.monotonic()
        delay = _chaos_delay()
        if delay:
            time.sleep(delay)
        now = time.monotonic()
        live = []
        for r in group:
            if r.deadline is not None and now > r.deadline:
                if self.metrics is not None:
                    self.metrics.deadline_expired.add()
                    self.metrics.errors.add()
                obs_events.emit("deadline_expired",
                                model=self._model_name,
                                trace_id=r.trace_id,
                                replica=lane.index, device=lane.device,
                                waited_ms=round(
                                    (now - r.enqueued) * 1000.0, 3))
                if r.future.set_running_or_notify_cancel():
                    r.future.set_exception(DeadlineExceeded(
                        "deadline passed after %.1f ms in queue"
                        % ((now - r.enqueued) * 1000.0)))
            else:
                live.append(r)
        if not live:
            return
        feeds = self._merge_feeds(live)
        total = sum(r.batch or 0 for r in live)
        t_run = time.monotonic()
        fetches = lane.predictor.run(feeds)
        t_run_end = time.monotonic()
        with self._cv:
            lane.batches += 1
            lane.rows += total
            lane.last_t = t_run_end
            dt = t_run_end - t_run
            lane.disp_ewma = dt if lane.disp_ewma is None \
                else 0.8 * lane.disp_ewma + 0.2 * dt
        if self.metrics is not None:
            cap = self._bucket_cap(total) if total else 0
            self.metrics.note_dispatch(
                n_requests=len(live), real_rows=total,
                padded_rows=max(cap - total, 0))
        self._scatter(live, fetches, total, lane, t_start, t_run,
                      t_run_end)

    def _lane_dead(self, lane, exc):
        """Mesh-member loss (SERVING.md "Mesh replicas"): the group is
        ONE replica, so the lane dies whole — marked dead (the router
        skips it from here on), its queued groups fail typed, sibling
        lanes keep serving.  Never wedges: a dead lane's workers exit
        cleanly instead of raising through _guarded."""
        with self._cv:
            if lane.dead is not None:
                return
            lane.dead = "%s: %s" % (type(exc).__name__, exc)
            leftovers = []
            while lane.ready:
                leftovers.extend(lane.ready.popleft())
            self._cv.notify_all()
        obs_events.emit("mesh_lane_dead", model=self._model_name,
                        replica=lane.index, device=lane.device,
                        error=str(exc))
        for r in leftovers:
            if r.future.set_running_or_notify_cancel():
                r.future.set_exception(exc)
            if self.metrics is not None:
                self.metrics.errors.add()

    def _worker(self, lane):
        while True:
            with self._cv:
                while not lane.ready:
                    if self._stopped or lane.dead is not None:
                        return
                    self._cv.wait(0.1)
                group = lane.ready.popleft()
                lane.inflight += 1
                self._cv.notify_all()
            try:
                self._dispatch(group, lane)
            except BaseException as e:
                for r in group:
                    if not r.future.done() and \
                            r.future.set_running_or_notify_cancel():
                        r.future.set_exception(e)
                if self.metrics is not None:
                    self.metrics.errors.add(len(group))
                if isinstance(e, MeshMemberLost):
                    self._lane_dead(lane, e)
            finally:
                with self._cv:
                    lane.inflight -= 1
                    self._cv.notify_all()

    # ------------------------------------------------------------------
    # lifecycle
    # ------------------------------------------------------------------

    def _busy(self):
        return (self._pending or self._carrying
                or any(l.ready or l.inflight for l in self._lanes))

    def drain(self, timeout=None):
        """Block until every queued, routed, and in-flight request has
        resolved — across all replica lanes."""
        deadline = None if timeout is None else time.monotonic() + timeout
        with self._cv:
            self._cv.notify_all()
            while self._busy():
                rem = None if deadline is None else \
                    max(deadline - time.monotonic(), 0.0)
                if rem == 0.0:
                    raise TimeoutError(
                        "batcher still has %d queued + %d lane-queued + "
                        "%d in-flight requests after %.1fs"
                        % (len(self._pending),
                           sum(len(l.ready) for l in self._lanes),
                           sum(l.inflight for l in self._lanes),
                           timeout))
                self._cv.wait(0.05 if rem is None else min(rem, 0.05))

    def close(self, drain=True, timeout=30.0):
        """Stop accepting; optionally finish everything queued first
        (the graceful-drain half of a hot swap or shutdown), then stop
        the router and lane workers.  With drain=False, queued requests
        fail with BatcherClosed."""
        with self._cv:
            self._closing = True
            self._cv.notify_all()
        if drain:
            self.drain(timeout)
        with self._cv:
            self._stopped = True
            leftovers = list(self._pending)
            self._pending.clear()
            for lane in self._lanes:
                while lane.ready:
                    leftovers.extend(lane.ready.popleft())
            self._cv.notify_all()
        for r in leftovers:
            if r.future.set_running_or_notify_cancel():
                r.future.set_exception(
                    BatcherClosed("server shut down before dispatch"))
            if self.metrics is not None:
                self.metrics.errors.add()
        self._router.join(timeout=5.0)
        for t in self._threads:
            t.join(timeout=5.0)


# ---------------------------------------------------------------------------
# continuous batching for autoregressive decode (SERVING.md "Continuous
# batching & streaming").  The DynamicBatcher above coalesces ONE-SHOT
# requests into one dispatch; generation inverts the shape — each
# request is MANY tiny steps over growing state, so the utilization
# lever is slot occupancy over time, not batch fill per dispatch.  The
# DecodeBatcher keeps one DecodeSession (slot-indexed KV cache,
# inference/decode.py) per replica lane and runs a continuous loop: a
# waiting request joins the RUNNING decode batch the step after any
# slot frees (EOS / max-new-tokens / deadline / client disconnect) —
# never a coalesce window, never waiting for the batch to drain.  The
# decode step is one fixed-shape executable over the whole slot table,
# so XLA compiles it once and every mix of requests reuses it.
# ---------------------------------------------------------------------------


_held = threading.local()


@contextlib.contextmanager
def _one_item():
    """What the calling thread puts, inside the block, on streams that a
    writer has taken (`DecodeStream.attach`) reaches that writer as ONE
    item at the block's end: one wake-up of one thread for a delivery's
    chunks, not one a chunk.  A block inside a block joins the outer
    one.  Streams nobody took are put on their own queues at once."""
    if getattr(_held, "by_sink", None) is not None:
        yield
        return
    _held.by_sink = by_sink = {}
    try:
        yield
    finally:
        _held.by_sink = None
        for sink, events in by_sink.items():
            sink.post(events)


class DecodeStream:
    """The caller's handle on one streaming generation: an event queue
    the owning lane feeds (token chunks, then exactly one terminal
    event), iterable as token-chunk lists.  ``result()`` collects the
    whole stream — the Future-shaped surface the server's one-shot
    `infer` path uses unchanged on decode models.

    A stream that a writer has taken (`attach`: the server's
    `infer_stream` verb) has no consumer of its own: its events go to
    the writer's one queue, and nobody wakes for one of them but the
    writer (SERVING.md "Streaming wire protocol")."""

    def __init__(self, trace_id, prompt_len, max_new_tokens):
        self.trace_id = trace_id
        self.prompt_len = int(prompt_len)
        self.max_new_tokens = int(max_new_tokens)
        self.obs_info = None     # stage timing attribution, at finish
        self.finish_reason = None
        self._q = queue_mod.Queue()
        # one entry a "tokens" item of `_q`, in its order: the lane's
        # (t_made, t_put) stamps of that chunk, None with tracing off
        self._stamps = collections.deque()
        self._tokens = []
        self._done = threading.Event()
        self._cancel = threading.Event()
        self._error = None
        # (sink, tag) once a writer has taken the stream; the lock
        # orders `attach` against the lane's puts
        self._sink = None
        self._lock = threading.Lock()

    # -- lane side ------------------------------------------------------

    def _put(self, kind, payload, stamps=None):
        with self._lock:
            taken = self._sink
            if taken is None:
                if kind == "tokens":
                    self._stamps.append(stamps)
                self._q.put((kind, payload))
                return
        sink, tag = taken
        held = getattr(_held, "by_sink", None)
        if held is None:
            sink.post([(tag, kind, payload, stamps)])
        else:
            held.setdefault(sink, []).append((tag, kind, payload, stamps))

    def _put_tokens(self, toks, stamps=None):
        """`stamps`, with tracing on: (end of the dispatch that made the
        chunk, now), on time.monotonic(); whoever sends the chunk takes
        them beside it (`take_stamps`, or the writer's event)."""
        toks = [int(t) for t in toks]
        self._tokens.extend(toks)
        self._put("tokens", toks, stamps)

    def _finish(self, reason, obs_info=None):
        self.finish_reason = reason
        self.obs_info = obs_info
        self._done.set()
        self._put("done", reason)

    def _fail(self, exc):
        self._error = exc
        self.finish_reason = "error"
        self._done.set()
        self._put("error", exc)

    # -- caller side ----------------------------------------------------

    def cancel(self):
        """Ask the owning lane to evict this request; the slot is freed
        (and zeroed) at the next dispatch boundary.  The server's stream
        handler calls this when the client connection dies mid-reply."""
        self._cancel.set()

    def cancelled(self):
        return self._cancel.is_set()

    def done(self):
        return self._done.is_set()

    @property
    def tokens(self):
        """Tokens generated so far (grows while streaming)."""
        return list(self._tokens)

    def attach(self, sink, tag):
        """Hand this stream's events to `sink` from now on:
        `sink.post([(tag, kind, payload, stamps), ...])` takes a list of
        events as one item, `stamps` the lane's (t_made, t_put) of a
        "tokens" event or None.  What the lane put before goes first,
        in order, as one item; the stream's own queue stays empty from
        here on."""
        with self._lock:
            events = []
            while not self._q.empty():
                kind, payload = self._q.get_nowait()
                events.append((tag, kind, payload,
                               self._stamps.popleft()
                               if kind == "tokens" and self._stamps
                               else None))
            self._sink = (sink, tag)
            if events:
                sink.post(events)

    def events(self, timeout=None):
        """Yield ("tokens", [ints]) chunks then one terminal ("done",
        reason) / ("error", exc) event.  `timeout` bounds the wait for
        EACH event."""
        while True:
            ev = self._q.get(timeout=timeout)
            yield ev
            if ev[0] != "tokens":
                return

    def take_stamps(self):
        """The lane's stamps of the "tokens" event `events()` handed out
        last, for a consumer that asks after EVERY such event:
        (t_made, t_put), or None where the lane took none.  (A stream
        the server's writer has taken hands them over in its events.)"""
        with self._lock:
            return self._stamps.popleft() if self._stamps else None

    def __iter__(self):
        """Token-chunk iterator; raises the stream's typed error at the
        point of failure."""
        for kind, payload in self.events():
            if kind == "tokens":
                yield payload
            elif kind == "error":
                raise payload

    def result(self, timeout=None):
        """Block to completion; returns the fetch-shaped reply (one
        int32 array of every generated token) or raises the stream's
        typed error — duck-typed as the batcher Future so the registry
        and the one-shot `infer` verb serve decode models unchanged."""
        if not self._done.wait(timeout=timeout):
            raise TimeoutError(
                "decode stream still running after %.1fs (%d tokens)"
                % (timeout or 0.0, len(self._tokens)))
        # drain keeps events() consumers and result() callers equivalent
        if self._error is not None:
            raise self._error
        return [np.asarray(self._tokens, np.int32)]


class _DecodeRequest:
    __slots__ = ("prompt", "max_new", "chunk", "deadline", "priority",
                 "trace_id", "stream", "enqueued", "t_admitted",
                 "t_first", "buf", "gen")

    def __init__(self, prompt, max_new, chunk, deadline, priority,
                 trace_id):
        self.prompt = prompt
        self.max_new = int(max_new)
        self.chunk = max(int(chunk), 1)
        self.deadline = deadline
        self.priority = int(priority)
        self.trace_id = trace_id or obs_tracing.new_trace_id()
        self.stream = DecodeStream(self.trace_id, len(prompt), max_new)
        self.enqueued = time.monotonic()
        self.t_admitted = None
        self.t_first = None
        self.buf = []
        self.gen = []


class _Prefill:
    """A prefill CALL of an admission, launched and not fetched yet
    (`DecodeBatcher._admit`): its requests (one, or a group's in arrival
    order) and their slots, whether the session queued it behind a call
    still unfetched, where its `serving/prefill_compute` spans start."""

    __slots__ = ("reqs", "slots", "ahead", "t0")

    def __init__(self, reqs, slots, ahead, t0):
        self.reqs = reqs
        self.slots = slots
        self.ahead = ahead
        self.t0 = t0


class _DecodeLane:
    """One replica's decode lane: its slot-table session plus the
    slot -> request assignment the continuous loop walks.  With a
    draft replica and spec_k >= 1 the session is a
    SpeculativeDecodeSession — the lane advances slots 1..k+1 tokens
    per round instead of exactly one."""

    __slots__ = ("index", "predictor", "session", "assigned", "steps",
                 "tokens", "spec", "degraded_noted", "last_step_t",
                 "step_ewma", "dead", "tp", "held", "early", "ahead")

    def __init__(self, index, predictor, n_slots, draft=None, spec_k=0):
        # error string once a mesh member died under this lane
        # (SERVING.md "Mesh replicas"): loop exited, streams failed
        # typed, sibling lanes unaffected
        self.dead = None
        self.last_step_t = None  # monotonic end of the last decode step
        # EWMA seconds per decode STEP (per trip under fusion) — the
        # deadline governor's estimate for clamping fused trip counts
        self.step_ewma = None
        self.index = index
        self.predictor = predictor
        # tensor-parallel lane: decode runs the partitioned program
        # (FLAGS.mesh_tp + a TP-splittable model on a mesh replica)
        self.tp = bool(getattr(predictor, "tp_active", False))
        if draft is not None and int(spec_k) >= 1:
            from ..inference.decode import SpeculativeDecodeSession
            self.session = SpeculativeDecodeSession(
                predictor, draft, n_slots, spec_k)
            self.spec = True
        else:
            self.session = predictor.new_session(n_slots)
            self.spec = False
        self.assigned = {}   # slot -> _DecodeRequest
        self.steps = 0
        self.tokens = 0
        self.degraded_noted = False
        # the newest dispatch's delivery, held back until the next
        # dispatch is launched (`DecodeBatcher._lane_iter`), and how
        # many dispatches were launched ahead of one
        self.held = None
        self.early = 0
        # prefills launched behind one not fetched yet (`_admit`)
        self.ahead = 0


class DecodeBatcher:
    """Slot-based continuous batching over one or more replica
    GenerativePredictors.  Admission control matches the DynamicBatcher
    contract (bounded queue, lowest-priority-first shed, shed-not-hang);
    past admission the lifecycle is streaming: prefill into a free slot,
    then ride the lane's running decode loop until EOS / max-new-tokens
    / deadline / cancel frees the slot for the next waiting request.

    ``continuous=False`` is the STATIC-batching baseline the bench
    lanes compare against: a lane only admits when it is idle, takes a
    full batch, and decodes until the LAST member finishes — the
    pre-continuous-batching serving shape (bench_zoo
    serving_decode_static).

    With ``draft_replicas``/``spec_k`` (SERVING.md "Speculative
    decoding") each lane runs a SpeculativeDecodeSession: per round the
    draft proposes k tokens, one batched target verify step scores all
    k+1 positions, and slots advance 1..k+1 committed tokens — the
    per-slot variable-accept bookkeeping below consumes each commit
    list in stream order with per-token EOS/max-new cuts, so the wire
    stream is bit-identical to the one-token-per-step path.  Draft
    failure degrades the lane to target-only decode within one round
    (`spec_degraded` event + counter), never wedging a stream.

    A dispatch is a WINDOW of decode steps (SERVING.md "Fused
    multi-step decode"; `DecodeSession.decode_fused`), and the lane
    picks its trips from its own slot table: min(cap, LARGEST
    remaining budget of the live slots), each slot's own budget riding
    beside it.  A slot that ends inside the window (its budget, its
    cache room, an EOS) stops in-graph and sits out the trips left; the
    dispatch ends when its last slot has stopped, so streams that end
    one by one do not each cost a dispatch of host work.  What that
    costs is the ender's dead slot-trips (the benchmark's
    `slots_busy_share`) and up to a window's wait for its terminal
    frame and for the newcomer that takes its slot.  A full lane whose
    last dispatch ended nobody launches the next one before it hands
    the last one's tokens to the streams (`_lane_iter`).  Joins, leaves,
    cancels and deadline evictions
    happen at dispatch boundaries, per-token EOS/max-new cuts land in stream
    order from the returned token block, and a per-lane EWMA of step
    time clamps the trips so no deadline overshoots by more than one
    dispatch (the overshoot lands on the `deadline_expired` event).
    Streams are those of one-trip dispatches token for token whatever
    joins or leaves.  ``fuse_steps`` pins the cap (tests; 1 = every
    dispatch one trip); None is the built-in `decode.STEP_WINDOW`.  A
    speculative lane runs its rounds host-driven unless ``fuse_steps``
    > 1 is pinned, which fuses the whole draft+verify round into one
    dispatch (`SpeculativeDecodeSession.step(fused=True)`)."""

    def __init__(self, predictor, replicas=None, n_slots=None,
                 max_queue=None, metrics=None, max_new_tokens=None,
                 continuous=True, draft=None, draft_replicas=None,
                 spec_k=None, fuse_steps=None):
        preds = list(replicas) if replicas else [predictor]
        self.predictor = predictor if predictor is not None else preds[0]
        self.n_slots = max(int(FLAGS.serving_decode_slots
                               if n_slots is None else n_slots), 1)
        self.max_queue = int(FLAGS.serving_max_queue
                             if max_queue is None else max_queue)
        self.max_new_cap = max(int(FLAGS.serving_max_new_tokens
                                   if max_new_tokens is None
                                   else max_new_tokens), 1)
        self.continuous = bool(continuous)
        self.metrics = metrics
        # the most trips one dispatch runs: the step executable's own
        # window unless a caller pins fewer (tests); a speculative lane
        # fuses its rounds only where a caller pinned a window
        from ..inference.decode import STEP_WINDOW
        self.fuse_steps = STEP_WINDOW if fuse_steps is None \
            else min(max(int(fuse_steps), 1), STEP_WINDOW)
        self.spec_fused = fuse_steps is not None and int(fuse_steps) > 1
        # speculative decoding (SERVING.md): one draft predictor per
        # replica lane (`draft_replicas`, or one shared `draft` for the
        # single-lane shape); spec_k is the draft depth per round
        self.spec_k = int(FLAGS.serving_spec_k if spec_k is None
                          else spec_k)
        drafts = list(draft_replicas) if draft_replicas else (
            [draft] * len(preds) if draft is not None else None)
        if drafts is not None and len(drafts) != len(preds):
            raise ValueError(
                "%d draft replicas for %d target replicas — the spec "
                "lanes pair one draft per target"
                % (len(drafts), len(preds)))
        if not drafts or self.spec_k < 1:
            drafts, self.spec_k = None, 0
        self.draft_replicas = drafts
        self._cv = threading.Condition()
        self._pending = collections.deque()
        self._lanes = [_DecodeLane(i, p, self.n_slots,
                                   draft=(drafts[i] if drafts else None),
                                   spec_k=self.spec_k)
                       for i, p in enumerate(preds)]
        self._closing = False
        self._stopped = False
        if metrics is not None:
            metrics.queue_depth_fn = lambda: len(self._pending)
            metrics.replica_stats_fn = self.replica_stats
            metrics.slot_occupancy_fn = self.slot_occupancy
            metrics.kv_cache_fn = self.kv_cache_info
            metrics.conv_state_fn = self.conv_state_bytes
        self._threads = [
            threading.Thread(
                target=_guarded(self._lane_loop,
                                lambda: self._model_name, "decode-lane"),
                args=(lane,), daemon=True,
                name="paddle-tpu-decode-lane%d" % lane.index)
            for lane in self._lanes]
        for t in self._threads:
            t.start()

    # ------------------------------------------------------------------

    @property
    def num_replicas(self):
        return len(self._lanes)

    @property
    def _model_name(self):
        return self.metrics.name if self.metrics is not None else None

    def batch_buckets(self):
        return self.predictor.prefill_buckets()

    def queue_depth(self):
        return len(self._pending)

    def slot_occupancy(self):
        """(occupied, total) across every LIVE lane — the occupancy
        gauge (a lane killed by mesh-member loss contributes no
        capacity)."""
        occupied = sum(len(l.assigned) for l in self._lanes)
        live = sum(1 for l in self._lanes if l.dead is None)
        return occupied, self.n_slots * live

    def lane_liveness(self):
        """Thread-level health (the `health` RPC verb): per decode
        lane, is its loop thread alive, how many slots are busy, and
        the age of its last completed decode step — a wedged lane
        reads as a growing last_step_age_s with busy slots."""
        now = time.monotonic()
        with self._cv:
            lanes = []
            for i, l in enumerate(self._lanes):
                t = self._threads[i] if i < len(self._threads) else None
                lanes.append({
                    "replica": l.index,
                    "alive": int(bool(t is not None and t.is_alive())),
                    "workers": 1,
                    "dead": l.dead,
                    "slots_busy": len(l.assigned),
                    "slots": self.n_slots,
                    "steps": l.steps,
                    "last_step_age_s":
                        round(now - l.last_step_t, 3)
                        if l.last_step_t is not None else None})
            return {"kind": "decode", "router_alive": True,
                    "queue_depth": len(self._pending),
                    "closing": self._closing, "lanes": lanes}

    def kv_cache_info(self):
        """(kv_cache_dtype, MEASURED slot-table bytes summed across
        this batcher's lanes) — the stats surface of the quantized-KV
        axis (QUANTIZE.md "Quantized KV cache"); bench_serving's
        --kv_dtype A/B reads the measured number against the static
        closed form."""
        dtype = str(getattr(self.predictor, "kv_cache_dtype",
                            "float32"))
        total = 0
        for lane in self._lanes:
            # a speculative lane wraps the target session; its cache
            # is the one the committed stream lives in
            sess = getattr(lane.session, "session", lane.session)
            cb = getattr(sess, "cache_bytes", None)
            if cb is not None:
                total += int(cb())
        return dtype, total

    def conv_state_bytes(self):
        """MEASURED bytes of the conv layers' slot state summed across
        this batcher's lanes: the second kind of slot state of a stack
        with conv layers (0 for any other), apart from the K/V bytes
        `kv_cache_info` reports."""
        return sum(int(getattr(lane.session, "conv_state_bytes",
                               lambda: 0)()) for lane in self._lanes)

    def _slots_busy_total(self):
        return sum(len(l.assigned) for l in self._lanes)

    def replica_stats(self):
        with self._cv:
            out = []
            for l in self._lanes:
                from ..inference.predictor import _device_label
                dev = _device_label(getattr(l.predictor, "device",
                                            None))
                out.append({"replica": l.index,
                            "device": dev,
                            "mesh": dev.count("+") + 1 if dev else 1,
                            "dead": l.dead,
                            "tp": l.tp,
                            "dispatch_ms":
                                round(l.step_ewma * 1000.0, 3)
                                if l.step_ewma is not None else None,
                            "inflight": len(l.assigned),
                            "queue": 0,
                            "batches": l.steps,
                            "early_launches": l.early,
                            "prefills_ahead": l.ahead,
                            "rows": l.tokens})
            return out

    # ------------------------------------------------------------------
    # submit side: the same admission-control contract as DynamicBatcher
    # ------------------------------------------------------------------

    def submit(self, tokens, max_new_tokens=None, deadline=None,
               priority=0, trace_id=None, chunk_tokens=None):
        """Enqueue one generation request.  Returns a DecodeStream.
        `max_new_tokens` is clamped to the server-side cap; `deadline`
        is an absolute time.monotonic() instant covering queue wait,
        prefill AND in-decode time — a streaming request past it is
        evicted from its slot mid-generation (the PR 8 deadline fix)."""
        prompt = np.asarray(tokens, np.int32).reshape(-1)
        if prompt.size < 1:
            raise ValueError("empty prompt")
        # reject unservable prompts synchronously (admission decisions
        # are immediate); also guarantees >= 1 generated token fits
        self.predictor.prompt_bucket(int(prompt.size))
        if prompt.size >= self.predictor.max_seq_len:
            raise ValueError(
                "prompt of %d tokens leaves no cache room to generate "
                "(max_seq_len %d)" % (prompt.size,
                                      self.predictor.max_seq_len))
        max_new = self.max_new_cap if max_new_tokens is None else \
            max(min(int(max_new_tokens), self.max_new_cap), 1)
        chunk = int(FLAGS.serving_stream_chunk_tokens
                    if chunk_tokens is None else chunk_tokens)
        req = _DecodeRequest(list(int(t) for t in prompt), max_new,
                             chunk, deadline, priority, trace_id)
        evicted = None
        with self._cv:
            if self._closing:
                raise BatcherClosed("model batcher is draining/retired")
            dead = [l.dead for l in self._lanes if l.dead is not None]
            if len(dead) == len(self._lanes):
                # every lane lost a mesh member: fail typed at
                # admission — nothing is left to ever serve this queue
                raise MeshMemberLost(
                    "every replica lane is dead (%s)" % dead[0])
            if len(self._pending) >= self.max_queue:
                victim = None
                for r in self._pending:
                    if r.priority < req.priority and \
                            (victim is None
                             or r.priority < victim.priority):
                        victim = r
                if victim is None:
                    if self.metrics is not None:
                        self.metrics.note_shed(priority=req.priority)
                    obs_events.emit("shed", model=self._model_name,
                                    priority=req.priority,
                                    trace_id=req.trace_id,
                                    queue=len(self._pending),
                                    slots_busy=self._slots_busy_total())
                    raise ServerOverloaded(
                        "decode queue full (%d waiting, max_queue=%d) — "
                        "priority-%d request shed; back off and retry"
                        % (len(self._pending), self.max_queue,
                           req.priority),
                        priority=req.priority)
                self._pending.remove(victim)
                evicted = victim
            self._pending.append(req)
            if self.metrics is not None:
                self.metrics.requests.add()
                self.metrics.streams.add()
            self._cv.notify_all()
        if evicted is not None:
            if self.metrics is not None:
                self.metrics.note_shed(priority=evicted.priority)
            obs_events.emit("shed", model=self._model_name,
                            priority=evicted.priority,
                            trace_id=evicted.trace_id, evicted=True,
                            by_priority=req.priority,
                            slots_busy=self._slots_busy_total())
            evicted.stream._fail(ServerOverloaded(
                "priority-%d request shed from a full decode queue by "
                "a priority-%d arrival (lowest-priority-first overload "
                "policy)" % (evicted.priority, req.priority),
                priority=evicted.priority))
        return req.stream

    # ------------------------------------------------------------------
    # the continuous loop (one thread per replica lane)
    # ------------------------------------------------------------------

    def _admissible(self, lane):
        if not self._pending:
            return False
        if self.continuous:
            return len(lane.assigned) < self.n_slots
        # static baseline: only an IDLE lane admits (then decodes the
        # whole batch to completion before admitting again)
        return not lane.assigned

    def _take_admits_locked(self, lane):
        """Pop the requests this lane admits right now (caller holds
        _cv — the `_locked` suffix is the lint-checked convention)."""
        room = self.n_slots - len(lane.assigned)
        out = []
        while self._pending and room > 0:
            out.append(self._pending.popleft())
            room -= 1
        return out

    def _emit_request_spans(self, req, lane, now):
        """Stage spans cut from contiguous monotonic stamps so
        queue_wait + prefill + decode tile serving/request exactly —
        the same tiling contract as the one-shot stage spans
        (OBSERVABILITY.md)."""
        model = self._model_name
        t_adm = req.t_admitted if req.t_admitted is not None \
            else req.enqueued
        t_first = req.t_first if req.t_first is not None else t_adm

        def _mk(name, t0, t1, parent="serving/request", **attrs):
            if model:
                attrs["model"] = model
            obs_tracing.stamp(name, t0, t1, kind="serving",
                              trace_id=req.trace_id, parent=parent,
                              **attrs)

        _mk("serving/queue_wait", req.enqueued, t_adm)
        _mk("serving/prefill", t_adm, t_first, replica=lane.index,
            prompt=len(req.prompt))
        _mk("serving/decode", t_first, now, replica=lane.index,
            tokens=len(req.gen))
        _mk("serving/request", req.enqueued, now, parent=None,
            replica=lane.index, prompt=len(req.prompt),
            tokens=len(req.gen), priority=req.priority)

    def _obs_info(self, req, lane, now):
        t_adm = req.t_admitted or now
        t_first = req.t_first or t_adm
        return {
            "trace_id": req.trace_id,
            "queue_wait_ms": round((t_adm - req.enqueued) * 1e3, 3),
            "prefill_ms": round((t_first - t_adm) * 1e3, 3),
            "decode_ms": round((now - t_first) * 1e3, 3),
            "server_ms": round((now - req.enqueued) * 1e3, 3),
            "ttft_ms": round((t_first - req.enqueued) * 1e3, 3),
            "tokens": len(req.gen),
            "replica": lane.index,
        }

    def _finish(self, lane, slot, req, reason, exc=None, made=None,
                at=None):
        """Terminal transition: flush, emit spans/metrics, free (and
        therefore ZERO) the slot so the next admit starts clean.  With
        tracing on it is one `serving/finish` span, the slot's release
        a `serving/slot_free` inside it.  `made` is the end of the
        dispatch (or prefill) whose tokens the flush carries; `at` =
        (round, order, enders) places a finish among those of one
        delivery (`_deliver`: its span then hangs under
        `serving/emit`), None anywhere else."""
        now = time.monotonic()
        traced = obs_tracing.enabled()
        rnd, order, enders = at or (lane.steps, 0, 1)
        # the flush and the terminal event: one item for the stream's
        # writer, handed over after the release (one with those of the
        # delivery's other enders, inside `_deliver`)
        with _one_item():
            if req.buf:
                req.stream._put_tokens(
                    req.buf, (now if made is None else made, now)
                    if traced else None)
                req.buf = []
            if slot is not None:
                t_free = time.monotonic() if traced else None
                lane.session.free(slot)
                lane.assigned.pop(slot, None)
                if traced:
                    obs_tracing.stamp(
                        "serving/slot_free", t_free, time.monotonic(),
                        kind="serving", trace_id=req.trace_id,
                        parent="serving/finish", slot=slot, round=rnd)
            if traced:
                self._emit_request_spans(req, lane, now)
            info = self._obs_info(req, lane, now)
            info["finish_reason"] = reason
            if exc is not None:
                if self.metrics is not None:
                    self.metrics.errors.add()
                    if isinstance(exc, DeadlineExceeded):
                        self.metrics.deadline_expired.add()
                req.stream.obs_info = info
                req.stream._fail(exc)
            else:
                if self.metrics is not None and reason != "cancelled":
                    self.metrics.note_completion(
                        latency_ms=info["server_ms"],
                        queue_wait_ms=info["queue_wait_ms"])
                req.stream._finish(reason, obs_info=info)
        if traced:
            obs_tracing.stamp(
                "serving/finish", now, time.monotonic(), kind="serving",
                trace_id=req.trace_id,
                parent="serving/lane_iter" if at is None
                else "serving/emit",
                replica=lane.index, round=rnd,
                slot=-1 if slot is None else slot, reason=reason,
                order=order, enders=enders)

    def _expire(self, lane, slot, req, now, **place):
        """Deadline eviction — in queue, at prefill, or MID-DECODE: the
        deadline covers in-decode time (the PR 8 admission-control
        fix), so a streaming request past it frees its slot within one
        step instead of pinning it to max_new_tokens.  `overshoot_ms`
        stamps how far past the deadline the eviction landed — under
        fused decode the check fires at window boundaries, and the
        trip-count clamp bounds this to about one dispatch."""
        obs_events.emit("deadline_expired", model=self._model_name,
                        trace_id=req.trace_id,
                        replica=lane.index,
                        tokens=len(req.gen),
                        waited_ms=round((now - req.enqueued) * 1e3, 3),
                        overshoot_ms=round((now - req.deadline) * 1e3, 3)
                        if req.deadline is not None else None)
        self._finish(lane, slot, req, "deadline", exc=DeadlineExceeded(
            "deadline passed after %.1f ms (%d tokens generated)"
            % ((now - req.enqueued) * 1e3, len(req.gen))), **place)

    def _admit(self, lane, admits):
        """Prefill the requests this pass admits: the same-bucket prompts
        of the pass as ONE call a group (`_prefill_calls`), the calls as a
        pipeline ONE deep: each is launched before the one ahead of it is
        fetched (`DecodeSession.launch_prefill` / `fetch_prefill`), so the
        device finds the next prefill in its queue when it ends one and
        does not wait for the host to copy first tokens back, do the
        requests' bookkeeping and pad the next prompts.  At most one call
        is queued behind the one the device runs: a first token waits for
        one launch, and two calls' temporaries are outstanding at most.
        With one admit the device calls are those of `prefill`, call for
        call.  A cancelled or expired admit is dropped BEFORE the grouping;
        everybody else lands in this pass.

        The `serving/prefill_compute` spans of an admission TILE, one a
        request: call i's run from the end of call i-1's fetch (from its
        own launch, where nothing was in flight) to the end of its own
        fetch, its members' an equal share of that each in arrival order,
        and carry `prompts`, the members of the call the request rode (1
        alone), and `ahead` = 1 where the session queued the CALL behind an
        unfetched one (`launch_prefill` says; a speculative session's
        launch half is a whole prefill and says no).

        A call that raises fails its own members typed and no others.  A
        member of the lane's mesh lost under a launch or a fetch: the
        call's requests fail typed, what is in flight is fetched (or fails
        with its own error), the admits not launched yet never touched
        the mesh and go back to the queue, in arrival order, for a
        surviving lane (if none survives, `_lane_dead` fails the whole
        queue typed), and the loop's member-loss handler retires the lane
        whole."""
        calls = self._prefill_calls(
            lane, [req for req in admits if self._still_wanted(lane, req)])
        flying = None
        try:
            for i, reqs in enumerate(calls):
                nxt = self._launch_prefill(lane, reqs, flying is not None)
                if nxt is not None:
                    prev, flying = flying, nxt
                    if prev is not None:
                        nxt.t0 = self._land_prefill(lane, prev)
            prev, flying = flying, None
            if prev is not None:
                self._land_prefill(lane, prev)
        except MeshMemberLost:
            left = {id(req) for reqs in calls[i + 1:] for req in reqs}
            with self._cv:
                for rem in reversed(admits):
                    if id(rem) in left:
                        self._pending.appendleft(rem)
                self._cv.notify_all()
            if flying is not None:
                try:
                    self._land_prefill(lane, flying)
                except MeshMemberLost:
                    pass
            raise

    def _still_wanted(self, lane, req):
        """Whether an admit is to be prefilled at all: one cancelled or
        past its deadline ends here, before it can enter a group."""
        req.t_admitted = now = time.monotonic()
        if req.stream.cancelled():
            self._finish(lane, None, req, "cancelled")
            return False
        if req.deadline is not None and now > req.deadline:
            self._expire(lane, None, req, now)
            return False
        return True

    def _prefill_calls(self, lane, reqs):
        """The prefill calls of an admission, each a list of requests.
        The admits sorted by prompt bucket (stably: arrival order inside a
        bucket), each bucket's run is cut into groups of the bucket's width
        (`GenerativePredictor.prefill_width`: what ONE call of the bucket's
        group executable takes); what is left of a run rides that
        executable too, padded, or goes a prompt a call (`decode.
        prefill_group`: PERF.md section 6, PR 55).  A pass in which nothing
        groups keeps the arrival order: every pass of a stack that prefills
        in chunks, of a lane on a mesh and of a speculative lane (its launch
        half is a whole blocking prefill of two sessions)."""
        from ..inference.decode import prefill_group
        pred = lane.session.predictor
        runs = {}
        for req in reqs:
            runs.setdefault(pred.prompt_bucket(len(req.prompt)),
                            []).append(req)
        calls = []
        for bucket in sorted(runs):
            run = runs[bucket]
            width = 1 if lane.spec else pred.prefill_width(bucket)
            while run:
                n = prefill_group(width, len(run))
                calls.append(run[:n])
                run = run[n:]
        if len(calls) == len(reqs):
            return [[req] for req in reqs]
        return calls

    def _prefill_spans(self, lane, p, t1, **more):
        """The `serving/prefill_compute` spans of one call of an admission
        (`_admit`), one a request: equal shares of p.t0 .. t1."""
        n = len(p.reqs)
        share = (t1 - p.t0) / n
        for j, req in enumerate(p.reqs):
            # a stack that prefills in chunks says how many a prompt takes
            chunks = lane.session.predictor.prefill_chunks(len(req.prompt))
            attrs = dict(more, chunks=chunks) if chunks else more
            obs_tracing.stamp("serving/prefill_compute", p.t0 + j * share,
                              t1 if j == n - 1 else p.t0 + (j + 1) * share,
                              kind="serving", trace_id=req.trace_id,
                              parent="serving/lane_iter",
                              model=self._model_name, replica=lane.index,
                              prompt=len(req.prompt), prompts=n,
                              ahead=int(p.ahead), **attrs)

    def _fail_prefill(self, lane, p, exc, span):
        """A call's launch or fetch raised: its spans (where `span`), its
        requests failed typed.  A lost mesh member is the lane's death
        too: raised on."""
        if span and obs_tracing.enabled():
            self._prefill_spans(lane, p, time.monotonic(),
                                error=type(exc).__name__)
        for req in p.reqs:
            self._finish(lane, None, req, "error", exc=exc)
        if isinstance(exc, MeshMemberLost):
            raise exc

    def _launch_prefill(self, lane, reqs, behind):
        """Admit one call's requests into free slots, first half: queue
        their prompts' prefill on the device (a group's as one call).
        Returns the `_Prefill` that `_land_prefill` takes, None for a call
        that failed here.  `behind`: an earlier call of this admission is
        not landed yet (its spans are open)."""
        now = time.monotonic()
        for req in reqs:
            req.t_admitted = now
        sess = lane.session
        p = _Prefill(reqs, sess.free_slots()[:len(reqs)], False, now)
        try:
            with obs_tracing.under("serving/prefill_compute",
                                   trace_id=reqs[0].trace_id):
                p.ahead = bool(
                    sess.launch_prefill(p.slots[0], reqs[0].prompt)
                    if len(reqs) == 1 else
                    sess.launch_prefill(p.slots, [r.prompt for r in reqs]))
        except BaseException as e:
            # (behind a call not landed yet this time lies in THAT call's
            # spans, which are still open)
            self._fail_prefill(lane, p, e, span=not behind)
            return None
        lane.ahead += p.ahead * len(reqs)
        return p

    def _land_prefill(self, lane, p):
        """Second half: wait for the oldest launched call, stream its
        requests' first tokens (the TTFT instant), in arrival order.
        Returns the end of its `serving/prefill_compute` spans."""
        sess = lane.session
        try:
            with obs_tracing.under("serving/prefill_compute",
                                   trace_id=p.reqs[0].trace_id):
                firsts = sess.fetch_prefill()
        except BaseException as e:
            end = time.monotonic()
            self._fail_prefill(lane, p, e, span=True)
            return end
        end = time.monotonic()
        if obs_tracing.enabled():
            self._prefill_spans(lane, p, end)
        for req, slot, first in zip(
                p.reqs, p.slots, firsts if len(p.reqs) > 1 else [firsts]):
            req.t_first = end
            if self.metrics is not None:
                self.metrics.note_prefill(
                    ttft_ms=(req.t_first - req.enqueued) * 1e3)
                self.metrics.note_tokens(1)
            lane.tokens += 1
            req.gen.append(first)
            req.buf.append(first)
            lane.assigned[slot] = req
            if first == self.predictor.eos_id:
                self._finish(lane, slot, req, "eos", made=req.t_first)
            elif req.max_new <= 1 or sess.room(slot) <= 0:
                self._finish(lane, slot, req, "length", made=req.t_first)
            elif len(req.buf) >= req.chunk:
                req.stream._put_tokens(
                    req.buf, (req.t_first, time.monotonic())
                    if obs_tracing.enabled() else None)
                req.buf = []
        return end

    def _emit_step_spans(self, lane, t0, t_draft_end, now, n_slots,
                         rnd, accepted=None, tokens=None, trips=None,
                         early=False):
        """Per-round step spans: `serving/decode_step` always (now a
        per-DISPATCH span: `tokens` emitted and `trips` loop
        iterations ride as attrs, the tokens-per-dispatch axis of the
        fused-decode win); on a speculative round its `serving/draft`
        + `serving/verify` children are cut from the same contiguous
        monotonic stamps so they TILE the round exactly (draft end ==
        verify start).  `rnd` is the lane's dispatch count, the `round`
        the session's `decode/*` spans of this dispatch inherited;
        `early` says the dispatch was launched ahead of the previous
        one's delivery (`_lane_iter`)."""
        attrs = {"model": self._model_name or "", "replica": lane.index,
                 "slots": n_slots, "round": rnd}
        if t_draft_end is not None:
            obs_tracing.stamp("serving/draft", t0, t_draft_end,
                              kind="serving", parent="serving/decode_step",
                              spec_k=lane.session.spec_k, **attrs)
            obs_tracing.stamp("serving/verify", t_draft_end, now,
                              kind="serving", parent="serving/decode_step",
                              accepted=accepted, **attrs)
        obs_tracing.stamp("serving/decode_step", t0, now, kind="serving",
                          parent="serving/lane_iter", tokens=tokens,
                          trips=trips, early=early, **attrs)

    def _emit_lane_iter(self, lane, t_iter, rnd, admits, emitted):
        """`serving/lane_iter`: one pass of the lane's loop that
        prefilled or dispatched, from the admission take to the notify
        (to the decision, where the delivery is held) — the parent of
        its `serving/prefill_compute`, `serving/decode_step` and
        `serving/emit`; what it holds beyond them is the lane's own
        host time."""
        obs_tracing.stamp("serving/lane_iter", t_iter, time.monotonic(),
                          kind="serving", model=self._model_name or "",
                          replica=lane.index, admits=admits,
                          emitted=emitted, round=rnd)

    def _note_degraded(self, lane):
        """First observation of a degraded spec session: latch the obs
        event + counter exactly once per lane (the chaos spec-fallback
        scenario pins both)."""
        if lane.degraded_noted or not lane.spec \
                or not lane.session.degraded:
            return
        lane.degraded_noted = True
        if self.metrics is not None:
            self.metrics.spec_degraded.add()
        obs_events.emit("spec_degraded", model=self._model_name,
                        replica=lane.index,
                        error=str(lane.session.degrade_error or ""))

    def _lane_dead(self, lane, exc):
        """Retire a lane whose mesh group lost a member (SERVING.md
        "Mesh replicas"): mark it dead, fail its in-flight streams
        typed — WITHOUT freeing slots, a free dispatches on the dead
        mesh — and fail everything queued once NO live lane remains to
        ever admit it.  Sibling lanes keep serving; the fleet
        controller rebuilds the lane from the model's persisted load
        spec."""
        with self._cv:
            if lane.dead is not None:
                return
            lane.dead = "%s: %s" % (type(exc).__name__, exc)
            victims = list(lane.assigned.values())
            lane.assigned.clear()
            pend = []
            if all(l.dead is not None for l in self._lanes):
                pend = list(self._pending)
                self._pending.clear()
            self._cv.notify_all()
        obs_events.emit(
            "mesh_lane_dead", model=self._model_name,
            replica=lane.index,
            device=_predictor_device_label(lane.predictor),
            error=str(exc))
        if self.metrics is not None and (victims or pend):
            self.metrics.errors.add(len(victims) + len(pend))
        with _one_item():
            for req in victims + pend:
                req.buf = []
                req.stream._fail(exc)

    def _lane_loop(self, lane):
        while True:
            try:
                if not self._lane_iter(lane):
                    return
            except Exception as e:
                # what a pass held back behind the launch that failed
                # reaches its streams before the failure does
                self._deliver(lane)
                if not isinstance(e, MeshMemberLost):
                    raise
                # one member of this lane's mesh is gone: the lane
                # dies WHOLE — typed failures, never a wedge — and
                # exits cleanly (no server_thread_death); the chaos
                # mesh-member-loss scenario pins this contract
                self._lane_dead(lane, e)
                return

    def _lane_iter(self, lane):
        """One pass of the continuous loop: admit + prefill (`_admit`:
        each prompt launched before the one ahead of it is fetched), one
        decode dispatch, then DECIDE what each slot got and whether it ends
        (list work: no queue, no lock, no device call) and DELIVER that
        to the streams (`_deliver`).  Returns False to stop.

        The order of delivery and the next launch follows from what the
        pass can see in its own slot table.  Where the decision ends
        nobody, every slot is assigned and the lane is not speculative,
        nothing can join or leave before the next dispatch, so its
        arguments are known.  The delivery is then HELD (`lane.held`)
        and the next pass launches first, delivers while the device
        runs, then fetches (SERVING.md "Fused multi-step decode").  The
        server's writer thread, which a delivery wakes, then runs
        beside the device and not in front of the launch.  In every
        other case (a finisher, a cancellation, an expiry, a free slot,
        a speculative lane, the lane's first dispatch) the pass
        delivers, finishes, and the next one admits, prefills and
        launches, as ever."""
        sess = lane.session
        eos = self.predictor.eos_id
        early = lane.held is not None
        if early:
            # every slot assigned and nobody ended a moment ago: nothing
            # to wait for, to admit or to drop at this boundary
            traced = obs_tracing.enabled()
            t_iter = time.monotonic() if traced else None
            admits = []
        else:
            with self._cv:
                while not lane.assigned and not self._admissible(lane):
                    if self._stopped:
                        return False
                    self._cv.wait(0.1)
                if self._stopped and not lane.assigned:
                    return False
                traced = obs_tracing.enabled()
                t_iter = time.monotonic() if traced else None
                admits = self._take_admits_locked(lane) \
                    if self._admissible(lane) else []
        # prefill OUTSIDE the lock: other lanes keep decoding
        self._admit(lane, admits)
        if not lane.assigned:
            self._note_degraded(lane)
            if traced and admits:
                self._emit_lane_iter(lane, t_iter, lane.steps,
                                     len(admits), 0)
            return True
        if not early:
            # dispatch-boundary housekeeping (SERVING.md "Fused
            # multi-step decode"): drop cancelled/expired streams BEFORE
            # burning a window on them; joins and leaves happen only
            # here (a pass that launches early looked a moment ago, when
            # it decided the last dispatch)
            nowb = time.monotonic()
            for slot, req in list(lane.assigned.items()):
                if req.stream.cancelled():
                    req.buf = []
                    self._finish(lane, slot, req, "cancelled")
                elif req.deadline is not None and nowb > req.deadline:
                    self._expire(lane, slot, req, nowb)
            if not lane.assigned:
                if traced and admits:
                    self._emit_lane_iter(lane, t_iter, lane.steps,
                                         len(admits), 0)
                return True
        n_act = len(lane.assigned)
        t0 = time.monotonic()
        # the same slow-worker chaos hook / deterministic per-step
        # device-cost stand-in as the one-shot lanes
        # (set_dispatch_delay — bench_serving --step_cost_ms; the
        # draft steps of a spec round price separately via
        # set_draft_delay — bench_serving --draft_cost_ms), plus
        # the per-DISPATCH host-cost stand-in (set_host_delay —
        # bench_serving --host_cost_ms) that fusion amortizes 1/N
        delay = _chaos_delay()
        host_delay = _host_chaos_delay()
        if host_delay:
            time.sleep(host_delay)
        trips = 1
        rnd = lane.steps
        accept = None
        # the dispatch is the region `serving/decode_step` covers (it is
        # stamped below, once the round's tokens are counted): the
        # session's `decode/*` spans find their parent and round here
        with obs_tracing.under("serving/decode_step", round=rnd):
            if lane.spec:
                toks2d, counts = sess.step(
                    step_delay=delay,
                    draft_delay=_draft_chaos_delay(),
                    fused=self.spec_fused)
                if sess.last_spec:
                    # per-round accept telemetry: k proposals per
                    # occupied slot, counts[s]-1 of them accepted
                    accept = (sess.spec_k * n_act,
                              int(counts.sum()) - n_act)
            else:
                # the window, from what the lane can see: run to the
                # round in which the LAST live slot must end (its
                # max_new / cache-room budget), the cap at most.  A slot
                # whose budget ends sooner stops in-graph at its own
                # trip and sits the rest out (`_step_math`): streams
                # that end one by one then cost dead slot-trips, not a
                # dispatch of host work each (PERF.md section 6, PR 43).
                # A newcomer waits for the window's end, one window at
                # most (PR 38).  The deadline governor: the lane's
                # EWMA step time clamps the trips so a deadlined stream
                # never overshoots by more than ~one dispatch
                cap = self.fuse_steps
                budget = np.zeros(self.n_slots, np.int32)
                max_trips = cap
                for slot, req in lane.assigned.items():
                    budget[slot] = min(req.max_new - len(req.gen),
                                       sess.room(slot), cap)
                    if req.deadline is not None and lane.step_ewma:
                        max_trips = min(max_trips, int(
                            (req.deadline - t0) / lane.step_ewma))
                max_trips = min(max_trips, int(budget.max()))
                sess.launch_fused(cap, budget=budget,
                                  max_trips=max(max_trips, 1))
                # the device runs this dispatch: the streams get the
                # last one's tokens now, if they were held
                self._deliver(lane)
                toks2d, counts, trips = sess.fetch_fused()
                if delay:
                    # the device-cost stand-in scales with the trips
                    # that actually ran (in-graph early exit included)
                    time.sleep(delay * trips)
        now = time.monotonic()
        lane.steps += 1
        lane.early += early
        lane.last_step_t = now
        # EWMA seconds per logical step (per trip): the fused
        # deadline governor's clamp input
        per_step = (now - t0) / max(trips, 1)
        lane.step_ewma = per_step if lane.step_ewma is None \
            else 0.5 * lane.step_ewma + 0.5 * per_step
        self._note_degraded(lane)
        # decide: what each slot got, who ends and why
        emitted = 0
        puts, ended = [], []
        for slot, req in lane.assigned.items():
            # a spec round commits 1..k+1 tokens per slot, a window up
            # to its trips; consume them in stream order with per-token
            # EOS/max-new cuts so the emitted stream is that of
            # one-token dispatches
            finished = None
            for tok in toks2d[slot, :int(counts[slot])].tolist():
                req.gen.append(tok)
                req.buf.append(tok)
                emitted += 1
                if tok == eos:
                    finished = "eos"
                    break
                if len(req.gen) >= req.max_new:
                    finished = "length"
                    break
            if req.stream.cancelled():
                # client gone: nobody reads the flush — just free
                req.buf = []
                ended.append((slot, req, "cancelled"))
            elif req.deadline is not None and now > req.deadline:
                ended.append((slot, req, "deadline"))
            elif finished is not None or sess.room(slot) <= 0:
                ended.append((slot, req, finished or "length"))
            elif len(req.buf) >= req.chunk:
                puts.append(req)
        lane.tokens += emitted
        lane.held = (rnd, now, trips, emitted, puts, ended, accept)
        # deliver now, unless the next dispatch's arguments are settled
        if lane.spec or ended or len(lane.assigned) < self.n_slots:
            self._deliver(lane, since=now)
        if traced:
            self._emit_step_spans(
                lane, t0, sess.last_draft_end if accept else None, now,
                n_act, rnd, accepted=accept[1] if accept else None,
                tokens=emitted, trips=trips, early=early)
            self._emit_lane_iter(lane, t_iter, rnd, len(admits), emitted)
        return True

    def _deliver(self, lane, since=None):
        """Hand the lane's newest decided dispatch (`lane.held`, if
        any) to its streams: the chunks due, then the terminal
        transitions (`_finish`'s flush comes after what was due before
        it, so a stream's frames stay in order), the dispatch's metrics
        and its `serving/emit` span, which starts at `since` (the
        dispatch's end, for a delivery made at once) or now (one that
        was held: it then lies inside the NEXT `serving/decode_step`).
        With tracing on every chunk carries the dispatch's end and the
        moment of its put (`_put_tokens`), and every ender's
        `serving/finish` says its place among them.  Streams that the
        server's writer has taken get all of it as two items (`_one_item`):
        a delivery wakes that one thread once or twice."""
        if lane.held is None:
            return
        (rnd, now, trips, emitted, puts, ended, accept), lane.held = \
            lane.held, None
        traced = obs_tracing.enabled()
        if since is None and traced:
            since = time.monotonic()
        # two items at most for the streams' writer, whatever the number
        # of streams: the chunks due, then what the enders leave behind
        with _one_item():
            for req in puts:
                req.stream._put_tokens(
                    req.buf, (now, time.monotonic()) if traced else None)
                req.buf = []
        with _one_item():
            for order, (slot, req, reason) in enumerate(ended):
                at = (rnd, order, len(ended))
                if reason == "deadline":
                    self._expire(lane, slot, req, now, made=now, at=at)
                else:
                    self._finish(lane, slot, req, reason, made=now,
                                 at=at)
        if traced:
            obs_tracing.stamp("serving/emit", since, time.monotonic(),
                              kind="serving", parent="serving/lane_iter",
                              replica=lane.index, round=rnd,
                              tokens=emitted, puts=len(puts),
                              enders=len(ended))
        if self.metrics is not None:
            self.metrics.decode_steps.add(trips)
            if accept:
                self.metrics.note_spec(*accept)
            # per-dispatch accounting: the tokens-per-dispatch
            # histogram is the direct readout of the fused-decode
            # amortization (TPD ~1 at N=1, ~N when fused)
            self.metrics.note_decode_dispatch(emitted)
            if emitted:
                self.metrics.note_tokens(emitted)
        with self._cv:
            self._cv.notify_all()

    # ------------------------------------------------------------------
    # lifecycle
    # ------------------------------------------------------------------

    def _busy(self):
        return bool(self._pending
                    or any(l.assigned for l in self._lanes))

    def drain(self, timeout=None):
        deadline = None if timeout is None else time.monotonic() + timeout
        with self._cv:
            self._cv.notify_all()
            while self._busy():
                rem = None if deadline is None else \
                    max(deadline - time.monotonic(), 0.0)
                if rem == 0.0:
                    raise TimeoutError(
                        "decode batcher still has %d queued + %d "
                        "in-slot requests after %.1fs"
                        % (len(self._pending),
                           sum(len(l.assigned) for l in self._lanes),
                           timeout))
                self._cv.wait(0.05 if rem is None else min(rem, 0.05))

    def close(self, drain=True, timeout=30.0):
        with self._cv:
            self._closing = True
            self._cv.notify_all()
        if drain:
            self.drain(timeout)
        with self._cv:
            self._stopped = True
            leftovers = list(self._pending)
            self._pending.clear()
            for lane in self._lanes:
                for req in lane.assigned.values():
                    req.stream.cancel()
            self._cv.notify_all()
        for req in leftovers:
            req.stream._fail(
                BatcherClosed("server shut down before dispatch"))
            if self.metrics is not None:
                self.metrics.errors.add()
        for t in self._threads:
            t.join(timeout=10.0)
