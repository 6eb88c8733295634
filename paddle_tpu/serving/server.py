"""Threaded inference server + client over the native wire protocol.

The serving front: the same length-prefixed typed-wire framing as the
parameter-server transport (distributed/rpc.py over native/wire.py — no
pickle ever touches a socket), carrying four commands:

  infer         {"cmd","model","feeds"{name->ndarray},"deadline_ms"?,
                 "version"?,"priority"?} -> {"ok","fetches"[ndarray...]}
                 or {"error","code"} with code in {"overloaded",
                 "deadline","no_model","bad_request","internal"};
                 an "overloaded" reply carries "shed_priority" — the
                 class the lowest-priority-first policy dropped
  load_model    {"cmd","name","path","version"?,"replicas"?,"devices"?}
                 — hot swap; replicas/devices are the device placement
                 spec (N, 'auto', or explicit device names)
  unload_model  {"cmd","name"} — drain then remove
  stats         {"cmd"} -> the ServingMetrics snapshot (now with
                 per-replica lane stats per model)
  health        {"cmd"} -> per-model SLO state (ok/degraded/breach,
                 burn rates) + lane/thread liveness + last-decode-step
                 age (OBSERVABILITY.md "SLOs & burn rates")
  flight        {"cmd","reason"?,"force"?} -> trigger a flight-recorder
                 post-mortem bundle; reply carries the committed path
  fleet         {"cmd","set_policy"?,"dry_run"?} -> fleet-controller
                 status (per-model state/replicas/paged, recent
                 actions, policies); set_policy maps model -> policy
                 body, dry_run flips rehearsal mode (SERVING.md
                 "Fleet controller")
  shutdown      graceful drain, then the server stops accepting

Admission control is the batcher's bounded queue: a request past
`FLAGS.serving_max_queue` is answered immediately with an "overloaded"
error (shed-not-hang).  Per-request deadlines bound BOTH queue wait and
the reply wait server-side; the client's `infer` reuses the shared
jittered-backoff RetryPolicy (utils/retry.py) to re-offer shed requests
until its deadline — jitter matters for the same reason it does on the
pserver plane: synchronized retries stampede a recovering server.

Graceful drain on shutdown: stop admitting, finish every queued
request, answer it, then exit — chaos-tested (tools/chaos.py FlakyProxy
+ slow-worker injection) in tests/test_serving.py.
"""

import os
import queue
import selectors
import socket
import socketserver
import threading
import time

import numpy as np

from ..distributed.rpc import _frame, _recv_msg, _send_msg
from ..flags import FLAGS
from ..native.wire import WireError
from ..obs import tracing as obs_tracing
from .batcher import (BatcherClosed, DeadlineExceeded, ServerOverloaded,
                      _guarded)
from .metrics import ServingMetrics
from .model_registry import ModelRegistry

__all__ = ["InferenceServer", "ServingClient", "ServingError",
           "StreamBroken"]

_CLOSE = object()


class ServingError(RuntimeError):
    """Server-side failure reported over the wire (non-typed codes)."""


class StreamBroken(ServingError):
    """An ``infer_stream`` connection died mid-generation.

    ``received`` counts the tokens already yielded — those are REAL
    (the server committed them); ``trace_id``/``backend`` identify the
    stream for re-placement.  Deliberately a ServingError subclass and
    NOT a ConnectionError: a generic reconnect-and-retry wrapper (the
    one-shot verbs' idiom) must never catch a broken stream and
    silently restart it from token 0 — that duplicates committed
    output.  Recovery is a NEW stream: through the federation frontend
    the same trace_id re-pins onto a live backend (affinity re-pin,
    paddle_tpu/federation/frontend.py), or the caller restarts
    explicitly with the received-token prefix in hand."""

    def __init__(self, message, trace_id=None, received=0,
                 backend=None):
        super(StreamBroken, self).__init__(message)
        self.trace_id = trace_id
        self.received = int(received)
        self.backend = backend


def _error_reply(exc):
    if isinstance(exc, ServerOverloaded):
        reply = {"error": str(exc), "code": "overloaded"}
        if getattr(exc, "priority", None) is not None:
            # which priority class was shed (the arrival, or the queued
            # request it evicted) — the client re-raises with it
            reply["shed_priority"] = int(exc.priority)
        return reply
    if isinstance(exc, (DeadlineExceeded, TimeoutError)):
        return {"error": str(exc), "code": "deadline"}
    if isinstance(exc, KeyError):
        return {"error": str(exc.args[0]) if exc.args else str(exc),
                "code": "no_model"}
    if isinstance(exc, (ValueError, TypeError, BatcherClosed)):
        return {"error": str(exc), "code": "bad_request"}
    return {"error": "%s: %s" % (type(exc).__name__, exc),
            "code": "internal"}


class InferenceServer:
    """One serving endpoint over a ModelRegistry.

    `model_root`: optional directory whose immediate subdirectories are
    loaded at start as models (subdir name == model name) — the
    "directory of artifacts -> multi-tenant service" contract."""

    def __init__(self, endpoint="127.0.0.1:0", model_root=None,
                 max_queue=None, deadline_ms=None, workers=None,
                 buckets=None, replicas=None, federation=None,
                 backend_id=None, capacity_mb=None):
        host, port = endpoint.rsplit(":", 1)
        self._addr = (host, int(port))
        # federation membership (paddle_tpu/federation): a frontend
        # endpoint to lease against — this server registers at start,
        # heartbeats its resident-model/queue payload, and deregisters
        # on shutdown.  None falls back to FLAGS.federation_frontend
        # (empty = standalone, the default).
        self._federation = federation if federation is not None \
            else (FLAGS.federation_frontend or None)
        self._backend_id = backend_id
        self._capacity_mb = capacity_mb
        self._fed_link = None
        self.metrics = ServingMetrics()
        # the unified telemetry surface (OBSERVABILITY.md): this
        # server's counters join the process-wide MetricsRegistry the
        # `metrics` RPC verb and tools/metrics_dump.py render
        from ..obs import registry as obs_registry
        self._obs_registry = obs_registry.default()
        self._obs_registry.attach_serving(self.metrics)
        # the judgment layer (OBSERVABILITY.md "SLOs & burn rates"):
        # a background monitor samples this server's counters into a
        # bounded time-series ring and evaluates declared SLOs
        # (FLAGS.serving_slo / slo.declare) into the ok/degraded/
        # breach state machine the `health` verb renders; breaches arm
        # the flight recorder.  FLAGS.slo_monitor=false opts out.
        self.slo = None
        if FLAGS.slo_monitor:
            from ..obs import slo as obs_slo
            self.slo = obs_slo.SLOMonitor.from_flags(self.metrics)
        # the control plane above the judgment layer (SERVING.md
        # "Fleet controller"): acts on the SLO/queue/occupancy/shed
        # signals through the registry's actuators — replica-set
        # scaling, cold-model paging, pressure degradation.
        # FLAGS.fleet_controller=false (default) keeps it off.
        self.fleet = None
        self._flight_provider = None
        # `replicas`: default placement spec for every model this server
        # loads (int N / 'auto' / explicit device list — SERVING.md
        # multi-chip serving); a load_model RPC can override per model
        self.registry = ModelRegistry(
            metrics=self.metrics, max_queue=max_queue,
            deadline_ms=deadline_ms, workers=workers, replicas=replicas)
        self._default_buckets = buckets
        self._model_root = model_root
        self._stopped = False
        self._draining = False
        self._server = None
        self._thread = None
        # the one thread that sends every stream's frames (started in
        # `start`, whatever the number of models, replicas and lanes)
        self._writer = None

    # ------------------------------------------------------------------

    def _load_root(self):
        root = self._model_root
        if not root:
            return
        for name in sorted(os.listdir(root)):
            path = os.path.join(root, name)
            if os.path.isdir(path):
                self.registry.load_model(name, path,
                                         buckets=self._default_buckets)

    def start(self, background=True):
        self._load_root()
        outer = self

        class Handler(socketserver.BaseRequestHandler):
            def handle(self):
                try:
                    while True:
                        msg = _recv_msg(self.request)
                        if msg.get("cmd") == "infer_stream":
                            # chunked reply: the stream handler owns the
                            # socket until its final frame (or the
                            # connection dies — which cancels the
                            # stream so its slot frees within one dispatch)
                            outer._handle_infer_stream(msg, self.request)
                            continue
                        try:
                            reply = outer._dispatch(msg)
                        except BaseException as e:
                            reply = _error_reply(e)
                        if reply is _CLOSE:
                            _send_msg(self.request, {"ok": True})
                            break
                        try:
                            _send_msg(self.request, reply)
                        except WireError as e:
                            # oversize outgoing frame: stream still in
                            # sync, surface the actionable message
                            _send_msg(self.request, {"error": str(e),
                                                     "code": "internal"})
                except WireError:
                    pass  # desynced incoming stream: drop the connection
                except (ConnectionError, EOFError, OSError):
                    pass

        class Server(socketserver.ThreadingTCPServer):
            allow_reuse_address = True
            daemon_threads = True
            # socketserver's default listen backlog of 5 makes a client
            # burst stall on SYN retransmits (seconds each) before the
            # request even reaches admission control; admission belongs
            # to the batcher's queue, not the kernel's
            request_queue_size = 128

        self._server = Server(self._addr, Handler)
        self._addr = self._server.server_address
        self._writer = _StreamWriter().start()
        self.metrics.tokens_sent_fn = lambda: self._writer.tokens_sent
        if self.slo is not None:
            self.slo.name = self.endpoint
            self.slo.start()
            self._obs_registry.attach_slo(self.slo)
        if FLAGS.fleet_controller:
            from .fleet import FleetController
            self.fleet = FleetController.from_flags(
                self.registry, self.metrics, slo=self.slo,
                name=self.endpoint)
            self.fleet.start()
            self._obs_registry.attach_fleet(self.fleet)
        # flight-recorder provider: every post-mortem bundle carries
        # this server's stats + registry/lane liveness + SLO timeline
        # (no-op while FLAGS.flight_dir is unset)
        from ..obs import flightrec
        self._flight_provider = "serving_%s" % \
            self.endpoint.replace(":", "_").replace(".", "-")
        flightrec.add_provider(self._flight_provider,
                               self._flight_snapshot)
        if self._federation:
            self._fed_link = _FederationLink(
                self, self._federation, backend_id=self._backend_id,
                capacity_mb=self._capacity_mb)
            self._fed_link.start()
            if self.fleet is not None:
                # scale/page policy belongs to the global tier once a
                # frontend owns placement (fleet.py delegation) —
                # degrade-before-shed stays local
                self.fleet.delegated_to = self._federation
        if background:
            self._thread = threading.Thread(target=self._serve,
                                            daemon=True)
            self._thread.start()
        else:
            self._serve()
        return self

    @property
    def endpoint(self):
        return "%s:%d" % (self._addr[0], self._addr[1])

    def _serve(self):
        self._server.timeout = 0.2
        with self._server:
            while not self._stopped:
                self._server.handle_request()

    def shutdown(self, drain=True, timeout=30.0):
        """Graceful stop: refuse new work, drain every queued request,
        then stop accepting connections."""
        self._draining = True
        if self._fed_link is not None:
            # de-lease FIRST: the frontend must stop placing before the
            # registry starts retiring lanes
            self._fed_link.stop(deregister=True)
            self._fed_link = None
        if self.fleet is not None:
            # stop acting BEFORE the drain: the controller must not
            # resize/page models the shutdown is retiring
            self.fleet.stop()
            self._obs_registry.detach_fleet(self.fleet)
        self.registry.close_all(drain=drain, timeout=timeout)
        if self._writer is not None:
            # every lane has ended, so every stream's terminal event is
            # queued: the writer sends them all, then joins
            self._writer.stop()
        self._stopped = True
        if self.slo is not None:
            self.slo.stop()
            self._obs_registry.detach_slo(self.slo)
        if self._flight_provider is not None:
            from ..obs import flightrec
            flightrec.remove_provider(self._flight_provider)
            self._flight_provider = None
        self._obs_registry.detach_serving(self.metrics)
        try:
            s = socket.create_connection(self._addr, timeout=1)
            s.close()
        except OSError:
            pass
        if self._thread is not None:
            self._thread.join(timeout=5.0)

    # ------------------------------------------------------------------

    def _health_snapshot(self):
        """The `health` verb payload: per-model SLO state + lane/thread
        liveness + last-decode-step age — the fleet controller's (and
        serving_top's) is-it-actually-serving readout, cheap enough to
        poll every second."""
        h = {"draining": bool(self._draining),
             # drain-vs-dead disambiguation (federation): accepting
             # False + an answering server = draining (streams still
             # finishing), no answer at all = dead — the frontend and
             # serving_top key on this instead of inferring from lease
             # age
             "accepting": not self._draining,
             "models": self.registry.health()}
        if self._federation:
            h["federation"] = {"frontend": self._federation,
                               "lease": (self._fed_link.lease
                                         if self._fed_link is not None
                                         else None)}
        if self.slo is not None:
            h["slo"] = self.slo.state()
            h["slo_monitor"] = {"running": self.slo.running,
                                "interval_s": self.slo.interval_s}
        if self.fleet is not None:
            # controller readout rides health too, so one poll (and
            # every flight bundle's server snapshot) carries it
            h["fleet"] = self.fleet.status()
        from ..obs import flightrec
        rec = flightrec.get_recorder()
        if rec is not None:
            h["flight"] = rec.stats()
        return h

    def _flight_snapshot(self):
        """Flight-recorder provider: what this server looked like at
        dump time (bundle file serving_<endpoint>.json)."""
        snap = {"endpoint": self.endpoint,
                "stats": self.metrics.snapshot(),
                "describe": self.registry.describe(),
                "health": self._health_snapshot()}
        if self.slo is not None:
            snap["slo_timeline"] = self.slo.timeline()
        return snap

    def _dispatch(self, msg):
        cmd = msg.get("cmd")
        if cmd == "infer":
            return self._handle_infer(msg)
        if cmd == "stats":
            return {"ok": True, "stats": self.metrics.snapshot(),
                    "models": self.registry.describe()}
        if cmd == "health":
            return {"ok": True, "health": self._health_snapshot()}
        if cmd == "fleet":
            # controller readout + policy/dry-run administration
            # (SERVING.md "Fleet controller"); reading works with the
            # controller disabled, administering it does not
            if msg.get("set_policy") or msg.get("dry_run") is not None:
                if self.fleet is None:
                    raise ValueError(
                        "fleet controller disabled — start the server "
                        "with FLAGS.fleet_controller=true")
                for model, spec in dict(
                        msg.get("set_policy") or {}).items():
                    self.fleet.set_policy(str(model), str(spec))
                if msg.get("dry_run") is not None:
                    self.fleet.dry_run = bool(msg["dry_run"])
            return {"ok": True,
                    "fleet": (self.fleet.status() if self.fleet
                              is not None else {"enabled": False})}
        if cmd == "flight":
            # manual post-mortem: dump a bundle NOW (cooldown bypassed
            # unless the caller asks otherwise); None = recorder
            # disabled (FLAGS.flight_dir unset) or dump failed
            from ..obs import flightrec
            path = flightrec.trigger(
                str(msg.get("reason") or "manual_rpc"),
                force=bool(msg.get("force", True)),
                endpoint=self.endpoint)
            return {"ok": True, "bundle": path,
                    "enabled": flightrec.get_recorder() is not None}
        if cmd == "metrics":
            # Prometheus-style text across training + serving — ONE
            # exposition (tools/metrics_dump.py renders it verbatim)
            return {"ok": True,
                    "text": self._obs_registry.prometheus_text()}
        if cmd == "trace":
            # span ring readout: a reply-visible trace_id resolves here
            # to its stage span tree (tools/trace_top.py)
            if msg.get("trace_id"):
                spans = obs_tracing.spans_for_trace(msg["trace_id"])
            else:
                spans = obs_tracing.recent_spans(
                    limit=int(msg.get("limit", 2048)),
                    kind=msg.get("kind") or None)
            return {"ok": True, "spans": spans,
                    "tracing": obs_tracing.stats()}
        if cmd == "load_model":
            if self._draining:
                raise BatcherClosed("server is draining")
            if msg.get("fleet_policy") and self.fleet is None:
                # typed rejection BEFORE any build work: a policy that
                # nothing will enforce is an operator error
                raise ValueError(
                    "load_model carried fleet_policy but the fleet "
                    "controller is disabled (FLAGS.fleet_controller)")
            entry = self.registry.load_model(
                msg["name"], msg["path"], version=msg.get("version"),
                buckets=msg.get("buckets") or self._default_buckets,
                replicas=msg.get("replicas"),
                devices=msg.get("devices"),
                decode_slots=msg.get("decode_slots"),
                decode_mode=msg.get("decode_mode"),
                precision=msg.get("precision"),
                ab_weight=msg.get("ab_weight"),
                draft=msg.get("draft"),
                spec_k=msg.get("spec_k"),
                kv_cache_dtype=msg.get("kv_cache_dtype"),
                fuse_steps=msg.get("fuse_steps"))
            if msg.get("fleet_policy"):
                self.fleet.set_policy(entry.name,
                                      str(msg["fleet_policy"]))
            reply = {"ok": True, "name": entry.name,
                     "version": entry.version,
                     "buckets": list(entry.predictor.batch_buckets()),
                     "replicas": len(entry.replicas),
                     "devices": entry.device_labels(),
                     # which numerics lane this version serves
                     # (QUANTIZE.md A/B axis)
                     "precision": entry.precision,
                     # what THIS load/flip cost against the persistent
                     # compile cache: a warm flip reads hits=N, misses=0
                     "compile_cache": dict(entry.compile_cache)}
            sizes = entry.mesh_sizes()
            if any(s > 1 for s in sizes):
                # the RESOLVED mesh shape (SERVING.md "Mesh replicas"):
                # members per replica lane, in route order — what a
                # 'mesh:2' spec actually packed on this host
                reply["mesh"] = sizes
            if entry.is_decode:
                reply["decode"] = True
                reply["decode_slots"] = entry.batcher.n_slots
                reply["max_seq_len"] = entry.predictor.max_seq_len
                reply["eos_id"] = entry.predictor.eos_id
                # the slot-table cache numerics this load serves
                # (QUANTIZE.md "Quantized KV cache")
                reply["kv_cache_dtype"] = str(getattr(
                    entry.predictor, "kv_cache_dtype", "float32"))
                # the cap of the window this load's lanes dispatch
                # (SERVING.md "Fused multi-step decode"; 1 = pinned to
                # one step a dispatch)
                reply["fuse_steps"] = int(getattr(
                    entry.batcher, "fuse_steps", 1))
                if getattr(entry.batcher, "spec_k", 0):
                    # speculative lanes armed: depth + draft artifact
                    reply["spec_k"] = entry.batcher.spec_k
                    reply["draft"] = entry.draft_path
            return reply
        if cmd == "unload_model":
            self.registry.unload_model(msg["name"])
            return {"ok": True}
        if cmd == "drain":
            # federation drain (SERVING.md "Federated serving"): stop
            # ACCEPTING without stopping — in-flight requests and
            # decode streams run to completion, new admissions refuse
            # with "overloaded"; `resume` flips the server back into
            # the placement set (tests, rolling maintenance)
            self._draining = not msg.get("resume")
            if self._fed_link is not None:
                # push the accepting flip now, not at the next beat
                self._fed_link.beat_soon()
            return {"ok": True, "accepting": not self._draining,
                    "draining": bool(self._draining)}
        if cmd == "page_model":
            # cluster-wide paging actuator (federation/global_fleet):
            # unload to the artifact path, keep the load spec — the
            # model faults back in on demand or by global decision
            self.registry.page_out(msg["name"])
            return {"ok": True, "paged": msg["name"]}
        if cmd == "resize_model":
            # the global controller re-placing one model's replica
            # budget on THIS host (build-warm-flip, fit-gated)
            entry = self.registry.resize_model(
                msg["name"], int(msg["replicas"]),
                precision=msg.get("precision"))
            return {"ok": True, "name": msg["name"],
                    "replicas": len(entry.replicas)}
        if cmd == "fault_model":
            # explicit fault-in (the global controller placing a cold
            # model on THIS host): replays the persisted lane spec
            self.registry.fault_in(
                msg["name"], trigger=str(msg.get("trigger") or "rpc"))
            return {"ok": True, "name": msg["name"],
                    "fault_in": dict(self.registry.last_fault_in.get(
                        msg["name"]) or {})}
        if cmd == "shutdown":
            # drain BEFORE replying so the client's ok means "all prior
            # requests answered"; the accept loop stops right after
            threading.Thread(target=self.shutdown, daemon=True).start()
            return {"ok": True, "draining": True}
        if cmd == "exit":
            self._stopped = True
            return _CLOSE
        return {"error": "unknown cmd %r" % cmd, "code": "bad_request"}

    def _handle_infer(self, msg):
        name = msg["model"]
        feeds = msg["feeds"]
        if not isinstance(feeds, dict) or not feeds:
            raise ValueError("infer needs a non-empty feeds dict")
        if self._draining:
            raise ServerOverloaded("server is draining — request refused")
        # trace id: carried in on the wire ("trace_id" field) or minted
        # at admission; echoed in the reply either way, so the caller
        # can resolve its latency into the span tree via the `trace`
        # verb / tools/trace_top.py (OBSERVABILITY.md)
        trace_id = str(msg.get("trace_id") or obs_tracing.new_trace_id())
        deadline_ms = msg.get("deadline_ms")
        deadline = None
        wait = 120.0  # never park a handler thread forever
        if deadline_ms is not None:
            deadline = time.monotonic() + float(deadline_ms) / 1000.0
            wait = float(deadline_ms) / 1000.0 + 5.0
        with obs_tracing.trace("serving/rpc", kind="serving",
                               trace_id=trace_id, model=name):
            future = self.registry.submit(
                name, feeds, version=msg.get("version"),
                deadline=deadline,
                priority=int(msg.get("priority", 0)),
                trace_id=trace_id,
                max_new_tokens=msg.get("max_new_tokens"),
                precision=msg.get("precision"))
            try:
                fetches = future.result(timeout=wait)
            except DeadlineExceeded:
                raise
            except TimeoutError:
                raise DeadlineExceeded(
                    "request did not complete within its %.0f ms "
                    "deadline"
                    % (deadline_ms if deadline_ms is not None
                       else wait * 1e3))
        reply = {"ok": True, "trace_id": trace_id,
                 "fetches": [np.ascontiguousarray(a) for a in fetches]}
        if getattr(future, "finish_reason", None):
            # decode model served through the one-shot verb: the whole
            # greedy stream comes back as fetches[0] plus why it ended
            reply["finish_reason"] = str(future.finish_reason)
        if msg.get("debug"):
            # opt-in latency attribution: the server-measured stage
            # timings ride back on the reply, so a client can see where
            # its time went without server access (queue_wait vs
            # compute vs batch_fill)
            reply["debug"] = dict(getattr(future, "obs_info", None)
                                  or {"trace_id": trace_id})
        return reply

    def _handle_infer_stream(self, msg, sock):
        """Chunked streaming generation (`infer_stream` verb): token
        deltas flush to the wire as the decode loop emits them —
        {"chunk": True, "seq": i, "tokens": [...], "trace_id"} frames,
        then exactly one terminal frame ({"ok": True, "done": True,
        "finish_reason", "new_tokens", ...} or {"error", "code",
        "done": True}).  Every frame carries the trace_id.  A dead
        client connection (send failure) CANCELS the stream, so its
        decode slot frees — and zeroes — within one dispatch."""
        trace_id = str(msg.get("trace_id") or obs_tracing.new_trace_id())
        stream = None
        try:
            if self._draining:
                raise ServerOverloaded(
                    "server is draining — request refused")
            tokens = msg.get("tokens")
            if tokens is None:
                raise ValueError(
                    "infer_stream needs a 'tokens' prompt array")
            deadline_ms = msg.get("deadline_ms")
            deadline = None
            if deadline_ms is not None:
                deadline = time.monotonic() + float(deadline_ms) / 1000.0
            stream = self.registry.submit_stream(
                msg["model"], tokens, version=msg.get("version"),
                max_new_tokens=msg.get("max_new_tokens"),
                deadline=deadline,
                priority=int(msg.get("priority", 0)),
                trace_id=trace_id,
                chunk_tokens=msg.get("stream_chunk_tokens"))
        except BaseException as e:
            reply = _error_reply(e)
            reply["done"] = True
            reply["trace_id"] = trace_id
            _send_msg(sock, reply)
            return
        # the server's one writer thread sends the stream's frames; this
        # thread sleeps until the terminal frame is out or the stream
        # broke (a dead or stuck peer: the writer has cancelled the
        # stream, and raising here drops the connection, as ever)
        out = self._writer.attach(sock, stream, trace_id,
                                  bool(msg.get("debug")))
        out.sent.wait()
        if out.error is not None:
            raise out.error


class _StreamOut:
    """What one request's frames waited for on their way out, folded by
    the writer thread that sends them (one thread, no lock) and landed
    as ONE `serving/stream_out` span at the request's end: a span a
    frame would overflow the ring.  A frame's way has three parts, on
    time.monotonic(): the dispatch's end to the lane's put (`lane_ms`),
    the put to the writer's turning to the chunk (`wake_ms`: the
    writer's wake-up and the frames of the pass ahead of it), and the
    encode and the socket write (`send_ms`)."""

    __slots__ = ("t_first", "frames", "tokens", "bytes", "lane_ms",
                 "wake_ms", "wake_max", "send_ms", "send_max")

    def __init__(self):
        self.t_first = None
        self.frames = self.tokens = self.bytes = 0
        self.lane_ms = self.wake_ms = self.wake_max = 0.0
        self.send_ms = self.send_max = 0.0

    def frame(self, stamps, t_have, t_sent, tokens, sent):
        """One chunk frame is out: `stamps` the lane's (t_made, t_put)
        of the chunk (None where it took none: the frame then counts
        with no lane or wake time), `t_have` when the writer turned to
        it, `sent` its bytes on the socket."""
        if stamps is not None:
            t_made, t_put = stamps
            if not self.frames:
                self.t_first = t_put
            wake = (t_have - t_put) * 1e3
            self.lane_ms += (t_put - t_made) * 1e3
            self.wake_ms += wake
            self.wake_max = max(self.wake_max, wake)
        send = (t_sent - t_have) * 1e3
        self.send_ms += send
        self.send_max = max(self.send_max, send)
        self.frames += 1
        self.tokens += tokens
        self.bytes += sent

    def land(self, trace_id, stream, end):
        """The span: from the request's first put (the writer's first
        look at the stream, where it sent no chunk) to `end`, its last
        send's return; a send that failed ends it where it failed."""
        if self.t_first is None:
            return
        attrs = {}
        replica = (stream.obs_info or {}).get("replica")
        if replica is not None:
            attrs["replica"] = replica
        obs_tracing.stamp(
            "serving/stream_out", self.t_first, end, kind="serving",
            trace_id=trace_id, parent="serving/request",
            frames=self.frames, tokens=self.tokens, bytes=self.bytes,
            lane_ms_sum=self.lane_ms, wake_ms_sum=self.wake_ms,
            wake_ms_max=self.wake_max, send_ms_sum=self.send_ms,
            send_ms_max=self.send_max, **attrs)


def _send_some(sock, data):
    """As many of `data`'s bytes as `sock` takes without waiting: 0
    where its buffer is full.  The one place a stream's frame goes on
    its socket."""
    try:
        return sock.send(data, socket.MSG_DONTWAIT)
    except (BlockingIOError, InterruptedError):
        return 0


class _Taken:
    """One stream the writer has taken, as the writer sees it (the tag
    of its events).  The writer thread alone touches it, but for `sent`
    and `error`: the stream's handler sleeps on the one and reads the
    other after it."""

    __slots__ = ("sock", "stream", "trace_id", "debug", "rec", "seq",
                 "unsent", "owed", "sent", "error")

    def __init__(self, sock, stream, trace_id, debug, rec):
        self.sock, self.stream = sock, stream
        self.trace_id, self.debug = trace_id, debug
        self.rec = rec              # _StreamOut, None with tracing off
        self.seq = 0
        # a peer that does not read: the bytes its socket has not taken
        # and the frames they belong to, oldest first
        self.unsent = bytearray()
        self.owed = []
        # set once the terminal frame is out or the stream broke: the
        # writer is done with the stream and drops its later events
        self.sent = threading.Event()
        self.error = None


class _StreamWriter:
    """The ONE thread of a server that encodes and sends the frames of
    every `infer_stream` request (SERVING.md "Streaming wire protocol").
    The lanes put their streams' events on its one queue, a delivery's
    as one or two items (`batcher._one_item`), and a pass of the writer
    takes everything queued and sends it in order: a dispatch with 96
    live streams wakes one thread, where a handler thread a stream made
    96 of them stand in line for the interpreter in front of the lane's
    next device call (PERF.md section 6, PR 45).

    It never waits for one socket.  A send takes what the socket takes
    at once (`_send_some`); what is left is kept with its stream, sent
    when the socket takes bytes again, and a stream with more than
    `MAX_UNSENT_FRAMES` frames held so is cancelled as a dead client's.
    A send that fails cancels its stream and wakes its handler, which
    drops the connection."""

    MAX_UNSENT_FRAMES = 64
    # shutdown: how long held bytes may still go out (selects of 0.1 s)
    STOP_GRACE_SELECTS = 50

    def __init__(self):
        self._items = queue.SimpleQueue()
        self._lock = threading.Lock()   # `_taken` and `_closed`
        self._taken = set()
        self._closed = False
        self._stopping = False
        self._thread = None
        # streams with unsent bytes.  While there are none the writer
        # sleeps on its queue; with some it sleeps in the selector, on
        # their sockets and on the wake-up pair, which `post` writes to
        # only then (`_selecting`): a hand-over is a queue put and no
        # system call, so the lane does not let go of the interpreter
        # between handing a delivery over and its own bookkeeping
        self._held = set()
        self._selecting = False
        # the TOKENS whose chunk frame is on its socket whole, over the
        # writer's life: what left the process (`ServingMetrics`
        # `tokens_sent_total`, the `tokens_total` of a pass's span).
        # This thread alone adds to it, tracing on or off
        self.tokens_sent = 0
        self._wake_r, self._wake_w = socket.socketpair()
        self._wake_r.setblocking(False)
        self._wake_w.setblocking(False)
        self._sel = selectors.DefaultSelector()
        self._sel.register(self._wake_r, selectors.EVENT_READ)

    def start(self):
        self._thread = threading.Thread(
            target=_guarded(self._run, lambda: "", "stream_writer"),
            name="serving-stream-writer", daemon=True)
        self._thread.start()
        return self

    def stop(self, timeout=10.0):
        """Send everything queued, then end; what a peer has not taken
        within the grace is dropped with its connection."""
        self._stopping = True
        self.post(())
        if self._thread is not None:
            self._thread.join(timeout=timeout)

    # -- the lanes' and the handlers' side --------------------------------

    def post(self, events):
        """One item: a list of (taken, kind, payload, stamps), and one
        wake-up."""
        self._items.put(events)
        if self._selecting:
            try:
                self._wake_w.send(b"\0")
            except OSError:
                pass    # wake-ups enough are waiting, or the writer is gone

    def attach(self, sock, stream, trace_id, debug):
        """Take `stream`: its frames go out on `sock` from here on.  The
        caller sleeps on the result's `sent`."""
        out = _Taken(sock, stream, trace_id, debug,
                     _StreamOut() if obs_tracing.enabled() else None)
        with self._lock:
            live = not self._closed
            if live:
                self._taken.add(out)
        if not live:
            out.error = ConnectionError("the server's stream writer "
                                        "has stopped")
            stream.cancel()
            out.sent.set()
        else:
            stream.attach(self, out)
        return out

    # -- the writer thread -------------------------------------------------

    def _wait(self, timeout=None):
        """Sleep until an item is queued or, with bytes held, a held-up
        socket takes them again (`timeout` bounds that sleep); returns
        (everything queued, the streams of such sockets)."""
        items, ready = [], []
        if not self._held:
            items.append(self._items.get())
        else:
            # the flag first, the look at the queue second: a `post`
            # that missed the one is seen by the other
            self._selecting = True
            try:
                if not self._items.empty():
                    timeout = 0
                for key, _ in self._sel.select(timeout):
                    if key.data is not None:
                        ready.append(key.data)
                    else:
                        try:
                            self._wake_r.recv(4096)
                        except BlockingIOError:
                            pass
            finally:
                self._selecting = False
        while not self._items.empty():
            items.append(self._items.get())
        return items, ready

    def _run(self):
        try:
            while not (self._stopping and self._items.empty()):
                self._write_pass(*self._wait())
            for _ in range(self.STOP_GRACE_SELECTS):
                if not self._held:
                    break
                self._write_pass(*self._wait(0.1))
        finally:
            with self._lock:
                self._closed = True
                left, self._taken = self._taken, set()
            for out in left:
                self._end(out, ConnectionError(
                    "the server's stream writer has stopped"))
            self._sel.close()
            self._wake_r.close()
            self._wake_w.close()

    def _write_pass(self, items, ready):
        """`items`, in order, after what held-up sockets take again
        (`ready`).  With tracing on it is one `serving/write_pass` span;
        off, it reads no clock.  The span's `tokens` are those of the
        chunk frames whose LAST byte went out in this pass (a frame a
        full socket held back counts where `_retry` finishes it),
        `tokens_total` the writer's running total after it: on the
        span's `t0`, time.monotonic(), they are the server's side of
        what a client's stamps count."""
        traced = obs_tracing.enabled()
        t0 = t_have = time.monotonic() if traced else None
        frames = enders = nbytes = 0
        sent_before = self.tokens_sent
        streams = set()
        for out in ready:
            if not out.sent.is_set():
                self._retry(out, traced)
        for events in items:
            for out, kind, payload, stamps in events:
                if out.sent.is_set():
                    continue
                streams.add(out)
                rec = out.rec if traced else None
                if rec is not None and rec.t_first is None:
                    rec.t_first = t_have
                try:
                    data = _frame(self._message(out, kind, payload))
                    owed = (kind, stamps, t_have,
                            len(payload) if kind == "tokens" else 0,
                            len(data))
                    took = 0 if out.unsent else _send_some(out.sock, data)
                    if took == len(data):
                        if traced:
                            t_have = time.monotonic()
                        self._out(out, owed, t_have, traced)
                    else:
                        self._hold(out, data[took:], owed)
                except Exception as e:
                    # a dead peer, an oversize frame, or anything else
                    # this stream's frame raises costs this stream
                    # alone; its handler raises it again
                    self._end(out, e)
                    continue
                nbytes += len(data)
                if kind == "tokens":
                    frames += 1
                else:
                    enders += 1
        if traced and (streams or ready):
            obs_tracing.stamp(
                "serving/write_pass", t0, time.monotonic(),
                kind="serving", frames=frames, streams=len(streams),
                enders=enders, bytes=nbytes, backlogged=len(self._held),
                tokens=self.tokens_sent - sent_before,
                tokens_total=self.tokens_sent,
                unsent_bytes=sum(len(o.unsent) for o in self._held))

    @staticmethod
    def _message(out, kind, payload):
        """The frame of one event, as `infer_stream` has always put it
        on the wire."""
        if kind == "tokens":
            msg = {"chunk": True, "seq": out.seq, "tokens": payload,
                   "trace_id": out.trace_id}
            out.seq += 1
        elif kind == "error":
            msg = _error_reply(payload)
            msg["done"] = True
            msg["trace_id"] = out.trace_id
            msg["new_tokens"] = len(out.stream.tokens)
        else:  # done
            msg = {"ok": True, "done": True, "trace_id": out.trace_id,
                   "finish_reason": str(payload),
                   "new_tokens": len(out.stream.tokens)}
            if out.debug:
                msg["debug"] = dict(out.stream.obs_info
                                    or {"trace_id": out.trace_id})
        return msg

    def _out(self, out, owed, t_sent, traced):
        """One frame is out whole: a chunk counts in its stream's
        record, the terminal frame ends the stream."""
        kind, stamps, t_have, tokens, size = owed
        if kind != "tokens":
            self._end(out)
            return
        self.tokens_sent += tokens
        if traced and out.rec is not None:
            out.rec.frame(stamps, t_have, t_sent, tokens, size)

    def _hold(self, out, rest, owed):
        """`out`'s socket is full: keep the bytes and the frame they end
        until it takes them (`_retry`), the stream's later frames behind
        them; past the bound the peer counts as dead."""
        if not out.unsent:
            self._sel.register(out.sock, selectors.EVENT_WRITE, out)
            self._held.add(out)
        out.unsent += rest
        out.owed.append(owed)
        if len(out.owed) > self.MAX_UNSENT_FRAMES:
            raise ConnectionError(
                "peer stopped reading: %d frames unsent"
                % len(out.owed))

    def _retry(self, out, traced):
        try:
            took = _send_some(out.sock, out.unsent)
        except OSError as e:
            self._end(out, e)
            return
        del out.unsent[:took]
        if out.unsent:
            return
        self._release(out)
        t_sent = time.monotonic() if traced else None
        owed, out.owed = out.owed, []
        for one in owed:
            self._out(out, one, t_sent, traced)

    def _release(self, out):
        if out in self._held:
            self._held.discard(out)
            self._sel.unregister(out.sock)

    def _end(self, out, error=None):
        """The terminal frame is out, or (`error`) the stream broke: the
        stream is cancelled then, so its slot frees at the lane's next
        dispatch boundary.  Either way the handler wakes."""
        self._release(out)
        out.unsent, out.owed = bytearray(), []
        if error is not None:
            out.error = error
            out.stream.cancel()
        with self._lock:
            self._taken.discard(out)
        if out.rec is not None:
            out.rec.land(out.trace_id, out.stream, time.monotonic())
        out.sent.set()


class _FederationLink:
    """Backend-side lease maintenance toward a federation frontend
    (paddle_tpu/federation): register at start, heartbeat every
    ``FLAGS.federation_heartbeat_ms`` carrying the serving payload
    (resident models + est_peak_mb + per-model queue/request counters,
    paged set, accepting flag), deregister on shutdown.  A heartbeat
    answered with code ``no_lease`` means the frontend already expired
    (or restarted past) this lease — the link re-registers on the next
    beat: the rejoin path, never silent serving on a dead lease."""

    def __init__(self, server, frontend, backend_id=None,
                 capacity_mb=None, heartbeat_s=None):
        self.server = server
        self.frontend = str(frontend)
        self.backend_id = backend_id
        self.capacity_mb = (float(FLAGS.federation_capacity_mb)
                            if capacity_mb is None
                            else float(capacity_mb))
        self.heartbeat_s = max(
            (float(FLAGS.federation_heartbeat_ms) / 1000.0
             if heartbeat_s is None else float(heartbeat_s)), 0.02)
        self.lease = None       # the granted {"backend_id","lease_id"}
        self._cli = ServingClient(self.frontend)
        self._stop = threading.Event()
        self._kick = threading.Event()
        self._thread = None

    # -- payload -------------------------------------------------------

    def _payload(self):
        """(models, paged, load): the lease's serving payload — what
        the frontend places by and the global controller senses by."""
        desc = self.server.registry.describe()
        snap = self.server.metrics.snapshot()
        models, paged = {}, []
        for name, d in desc.items():
            if d.get("paged"):
                paged.append(name)
                continue
            models[name] = {"replicas": int(d.get("replicas") or 1),
                            "decode": bool(d.get("decode"))}
        queue_depth = requests = 0
        for key, m in (snap.get("models") or {}).items():
            qd = int(m.get("queue_depth") or 0)
            rq = int(m.get("requests") or 0)
            queue_depth += qd
            requests += rq
            plain = m.get("model", key)
            info = models.get(plain)
            if info is not None:
                info["queue_depth"] = info.get("queue_depth", 0) + qd
                info["requests"] = info.get("requests", 0) + rq
                if m.get("est_peak_mb") is not None:
                    info["est_peak_mb"] = float(m["est_peak_mb"])
        load = {"queue_depth": queue_depth, "requests": requests}
        return models, paged, load

    # -- the beat ------------------------------------------------------

    def _register(self, models, paged, load):
        host, port = self.server._addr
        if host in ("0.0.0.0", "::"):
            host = "127.0.0.1"  # wildcard bind: advertise loopback
        reply = self._cli.call({
            "cmd": "register", "host": host, "port": int(port),
            "backend_id": self.backend_id,
            "capacity_mb": self.capacity_mb,
            "models": models, "paged": paged, "load": load})
        self.lease = {"backend_id": reply["backend_id"],
                      "lease_id": reply["lease_id"],
                      "ttl_s": reply.get("ttl_s")}
        self.backend_id = reply["backend_id"]

    def _beat(self):
        models, paged, load = self._payload()
        if self.lease is None:
            self._register(models, paged, load)
            return
        try:
            self._cli.call({
                "cmd": "heartbeat",
                "backend_id": self.lease["backend_id"],
                "lease_id": self.lease["lease_id"],
                "models": models, "paged": paged,
                "accepting": not self.server._draining,
                "load": load})
        except ServingError as e:
            if getattr(e, "code", None) == "no_lease":
                # expired under us (missed beats / frontend restart):
                # rejoin with a fresh lease right away
                self.lease = None
                self._register(models, paged, load)
            else:
                raise

    def beat_soon(self):
        """Wake the loop now (drain flips must not wait out a beat)."""
        self._kick.set()

    def _run(self):
        while not self._stop.is_set():
            self._kick.wait(self.heartbeat_s)
            self._kick.clear()
            if self._stop.is_set():
                return
            try:
                self._beat()
            except Exception:
                # frontend unreachable: drop the socket, retry next
                # beat — the lease expires frontend-side meanwhile,
                # which is exactly the contract
                self._cli.close()

    def start(self):
        try:
            self._beat()  # eager first register — placeable at return
        except Exception:
            self._cli.close()
        self._thread = threading.Thread(
            target=self._run, daemon=True,
            name="paddle-tpu-fedlink-%s" % self.frontend)
        self._thread.start()
        return self

    def stop(self, deregister=False, timeout=2.0):
        self._stop.set()
        self._kick.set()
        t = self._thread
        if t is not None:
            t.join(timeout=timeout)
        self._thread = None
        if deregister and self.lease is not None:
            try:
                self._cli.call({"cmd": "deregister",
                                "backend_id": self.lease["backend_id"],
                                "lease_id": self.lease["lease_id"]})
            except Exception:
                pass  # frontend gone: the TTL cleans up
        self.lease = None
        self._cli.close()


class ServingClient:
    """Wire client for InferenceServer.  Connections are thread-local
    (same rationale as RPCClient: a blocking round-trip per call, one
    socket per (thread, endpoint)).

    `infer` semantics: with a deadline, shed ("overloaded") replies and
    connection failures are retried under the shared jittered-backoff
    RetryPolicy until the deadline; without one, a shed surfaces
    immediately as ServerOverloaded so the caller owns the policy."""

    def __init__(self, endpoint, deadline_ms=None, retry_policy=None):
        self.endpoint = endpoint
        self.deadline_ms = deadline_ms
        self.last_trace_id = None
        self.last_stream_info = None  # final infer_stream frame metadata
        self._policy = retry_policy
        self._tls = threading.local()

    def _conn(self):
        s = getattr(self._tls, "sock", None)
        if s is None:
            host, port = self.endpoint.rsplit(":", 1)
            s = socket.create_connection((host, int(port)),
                                         timeout=FLAGS.rpc_deadline)
            self._tls.sock = s
        return s

    def _drop_conn(self):
        s = getattr(self._tls, "sock", None)
        self._tls.sock = None
        if s is not None:
            try:
                s.close()
            except OSError:
                pass

    def _call_once(self, msg):
        s = self._conn()
        try:
            _send_msg(s, msg)
            reply = _recv_msg(s)
        except (ConnectionError, EOFError, OSError, WireError):
            self._drop_conn()
            raise
        if "error" in reply:
            code = reply.get("code")
            if code == "overloaded":
                raise ServerOverloaded(reply["error"],
                                       priority=reply.get("shed_priority"))
            if code == "deadline":
                raise DeadlineExceeded(reply["error"])
            err = ServingError("%s (code=%s)" % (reply["error"], code))
            err.code = code  # typed dispatch (federation no_lease etc.)
            raise err
        return reply

    def call(self, msg):
        """One-shot forward of a raw verb dict — NO retry policy: the
        federation frontend's forwarding primitive (spillover policy
        owns the retries, the transport must not)."""
        return self._call_once(dict(msg))

    def _call(self, msg, retry_deadline=None, retry_on=()):
        if retry_deadline is None:
            return self._call_once(msg)
        from ..utils.retry import default_rpc_policy
        policy = self._policy or default_rpc_policy(
            max_attempts=1 << 20, max_delay=0.5)
        return policy.call(
            lambda: self._call_once(msg),
            retry_on=(ConnectionError, OSError, EOFError) + tuple(retry_on),
            on_retry=lambda e, attempt: self._drop_conn()
            if isinstance(e, (ConnectionError, OSError, EOFError))
            else None,
            deadline=retry_deadline)

    def infer_stream(self, model, tokens, max_new_tokens=None,
                     deadline_ms=None, version=None, priority=None,
                     trace_id=None, chunk_tokens=None, debug=False):
        """Streaming generation: returns an iterator yielding token-
        delta lists as the server decodes them (the `infer_stream`
        verb's chunk frames).  The final frame's metadata lands on
        ``self.last_stream_info`` (finish_reason, new_tokens, trace_id,
        + server stage timings with ``debug=True``) when the iterator
        completes.  A mid-stream error surfaces as the typed exception
        (ServerOverloaded / DeadlineExceeded / ServingError) at the
        point of failure — tokens already yielded are real.  Closing
        the iterator early drops the connection, which tells the server
        to evict the request from its decode slot.

        The streaming reply uses a dedicated connection (frames would
        desync the request/reply socket), torn down when the stream
        ends or the iterator is closed."""
        msg = {"cmd": "infer_stream", "model": model,
               "tokens": np.ascontiguousarray(
                   np.asarray(tokens, np.int32))}
        if max_new_tokens is not None:
            msg["max_new_tokens"] = int(max_new_tokens)
        if deadline_ms is not None:
            msg["deadline_ms"] = float(deadline_ms)
        if version is not None:
            msg["version"] = version
        if priority is not None:
            msg["priority"] = int(priority)
        if trace_id is not None:
            msg["trace_id"] = str(trace_id)
        if chunk_tokens is not None:
            msg["stream_chunk_tokens"] = int(chunk_tokens)
        if debug:
            msg["debug"] = True
        self.last_stream_info = None

        def _gen():
            host, port = self.endpoint.rsplit(":", 1)
            s = socket.create_connection((host, int(port)),
                                         timeout=FLAGS.rpc_deadline)
            finished = False
            received = 0  # tokens already yielded — committed output
            try:
                try:
                    _send_msg(s, msg)
                except (ConnectionError, EOFError, OSError,
                        WireError) as e:
                    raise StreamBroken(
                        "stream to %s broke before placement: %s"
                        % (self.endpoint, e),
                        trace_id=msg.get("trace_id"), received=0)
                while True:
                    try:
                        reply = _recv_msg(s)
                    except (ConnectionError, EOFError, OSError,
                            WireError) as e:
                        # the connection died MID-STREAM.  This must
                        # never look like a retryable transport error:
                        # a reconnect would restart the stream from
                        # token 0 and duplicate the `received` tokens
                        # already committed.  Typed StreamBroken makes
                        # generic (ConnectionError, OSError) retry
                        # loops pass it through; re-placement is the
                        # federation frontend's affinity re-pin.
                        finished = True
                        self.last_stream_info = {
                            "code": "stream_broken",
                            "new_tokens": received,
                            "trace_id": msg.get("trace_id")}
                        raise StreamBroken(
                            "stream to %s broke after %d token(s): %s"
                            % (self.endpoint, received, e),
                            trace_id=msg.get("trace_id"),
                            received=received)
                    if "error" in reply:
                        finished = True
                        self.last_stream_info = {
                            k: reply[k] for k in
                            ("trace_id", "new_tokens", "code",
                             "backend")
                            if k in reply}
                        self.last_trace_id = reply.get("trace_id")
                        code = reply.get("code")
                        if code == "overloaded":
                            raise ServerOverloaded(
                                reply["error"],
                                priority=reply.get("shed_priority"))
                        if code == "deadline":
                            raise DeadlineExceeded(reply["error"])
                        if code == "stream_broken":
                            # frontend-relayed backend death: same
                            # typed surface as a direct break
                            raise StreamBroken(
                                reply["error"],
                                trace_id=reply.get("trace_id"),
                                received=received,
                                backend=reply.get("backend"))
                        raise ServingError("%s (code=%s)"
                                           % (reply["error"], code))
                    if reply.get("chunk"):
                        toks = [int(t) for t in reply["tokens"]]
                        received += len(toks)
                        yield toks
                        continue
                    finished = True
                    self.last_stream_info = {
                        k: v for k, v in reply.items() if k != "ok"}
                    self.last_trace_id = reply.get("trace_id")
                    return
            finally:
                # early close (or any exit): this connection never
                # carries another request — a dropped socket is also
                # the eviction signal for an abandoned stream
                try:
                    s.close()
                except OSError:
                    pass
                if not finished:
                    pass  # server notices the dead socket on next flush

        return _gen()

    def infer(self, model, feeds, deadline_ms=None, version=None,
              retry_sheds=None, priority=None, debug=False,
              trace_id=None, max_new_tokens=None, precision=None):
        """Run one request.  Returns the fetch list; with
        ``debug=True`` returns ``(fetches, info)`` where ``info`` is
        the server-measured latency attribution (trace_id,
        queue_wait_ms, compute_ms, batch_fill, replica ...) — the
        client-side half of OBSERVABILITY.md's latency story.
        ``trace_id`` pins a caller-minted id (propagated end to end and
        echoed back); the reply's id is also kept on
        ``self.last_trace_id`` for the plain return shape."""
        deadline_ms = self.deadline_ms if deadline_ms is None \
            else deadline_ms
        msg = {"cmd": "infer", "model": model,
               "feeds": {k: np.ascontiguousarray(np.asarray(v))
                         for k, v in feeds.items()}}
        if version is not None:
            msg["version"] = version
        if precision is not None:
            # pin the request to one numerics lane ('fp32' / 'int8');
            # without it the server's A/B weights route (QUANTIZE.md)
            msg["precision"] = str(precision)
        if max_new_tokens is not None:
            # decode models through the one-shot verb: the whole greedy
            # stream returns as fetches[0]
            msg["max_new_tokens"] = int(max_new_tokens)
        if priority is not None:
            # forwarded to admission control: larger = more important;
            # under overload the server sheds lowest-priority-first
            msg["priority"] = int(priority)
        if debug:
            msg["debug"] = True
        if trace_id is not None:
            msg["trace_id"] = str(trace_id)
        retry_deadline = None
        retry_on = ()
        if deadline_ms is not None:
            msg["deadline_ms"] = float(deadline_ms)
            retry_deadline = time.monotonic() + float(deadline_ms) / 1000.0
            if retry_sheds is None or retry_sheds:
                retry_on = (ServerOverloaded,)
        elif retry_sheds:
            raise ValueError("retry_sheds needs a deadline_ms to bound it")
        reply = self._call(msg, retry_deadline=retry_deadline,
                           retry_on=retry_on)
        self.last_trace_id = reply.get("trace_id")
        fetches = list(reply["fetches"])
        if debug:
            return fetches, dict(reply.get("debug") or {})
        return fetches

    def load_model(self, name, path, version=None, buckets=None,
                   replicas=None, devices=None, decode_slots=None,
                   decode_mode=None, precision=None, ab_weight=None,
                   draft=None, spec_k=None, kv_cache_dtype=None,
                   fuse_steps=None, fleet_policy=None):
        msg = {"cmd": "load_model", "name": name, "path": path}
        if fleet_policy is not None:
            # per-model fleet policy body riding the load (SERVING.md
            # "Fleet controller"), e.g. 'max_replicas=4,page_ttl_s=600'
            msg["fleet_policy"] = str(fleet_policy)
        if kv_cache_dtype is not None:
            # decode artifacts: slot-table cache numerics for this
            # load — 'fp32'/'float32' or 'int8' (QUANTIZE.md)
            msg["kv_cache_dtype"] = str(kv_cache_dtype)
        if draft is not None:
            # speculative decoding: draft artifact path (SERVING.md);
            # the server pairs one draft replica per target replica
            msg["draft"] = str(draft)
        if spec_k is not None:
            msg["spec_k"] = int(spec_k)
        if fuse_steps is not None:
            # fused multi-step decode window per dispatch (SERVING.md
            # "Fused multi-step decode"; 1 keeps the classic loop)
            msg["fuse_steps"] = int(fuse_steps)
        if version is not None:
            msg["version"] = version
        if precision is not None:
            # lane override; normally auto-detected from the artifact
            msg["precision"] = str(precision)
        if ab_weight is not None:
            # this lane's share of default-routed traffic (A/B canary)
            msg["ab_weight"] = float(ab_weight)
        if buckets is not None:
            msg["buckets"] = [int(b) for b in buckets]
        if replicas is not None:
            # placement spec: int N, 'auto', or 'cpu:0,cpu:1' string
            msg["replicas"] = replicas if isinstance(replicas, str) \
                else int(replicas)
        if devices is not None:
            msg["devices"] = [str(d) for d in devices]
        if decode_slots is not None:
            msg["decode_slots"] = int(decode_slots)
        if decode_mode is not None:
            # "static" = the static-batch baseline (bench lanes only)
            msg["decode_mode"] = str(decode_mode)
        return self._call(msg)

    def unload_model(self, name):
        return self._call({"cmd": "unload_model", "name": name})

    def drain(self, resume=False):
        """Flip the server out of (or with ``resume=True`` back into)
        the accepting state: in-flight work finishes, new admissions
        refuse — the federation drain verb (SERVING.md)."""
        return self._call({"cmd": "drain", "resume": bool(resume)})

    def page_model(self, name):
        """Page one model out to its artifact path (load spec kept —
        it faults back in on demand)."""
        return self._call({"cmd": "page_model", "name": name})

    def fault_model(self, name, trigger="rpc"):
        """Fault one paged model back in on this server (the global
        controller's cross-host placement actuator)."""
        return self._call({"cmd": "fault_model", "name": name,
                           "trigger": str(trigger)})

    def stats(self):
        return self._call({"cmd": "stats"})

    def health(self):
        """Per-model SLO state + lane liveness (the `health` verb's
        payload): {"draining", "models": {...}, "slo": {...},
        "flight": {...}} — see SERVING.md."""
        return self._call({"cmd": "health"})["health"]

    def fleet(self, set_policy=None, dry_run=None):
        """Fleet-controller readout/administration (the `fleet` verb):
        returns the controller status dict ({"enabled": False} when
        the server runs without one).  `set_policy` maps model name ->
        policy body ('min_replicas=1,max_replicas=4,page_ttl_s=600');
        `dry_run` flips rehearsal mode.  Both require the controller
        to be enabled server-side."""
        msg = {"cmd": "fleet"}
        if set_policy:
            msg["set_policy"] = {str(k): str(v)
                                 for k, v in dict(set_policy).items()}
        if dry_run is not None:
            msg["dry_run"] = bool(dry_run)
        return self._call(msg)["fleet"]

    def set_fleet_policy(self, model, spec):
        """Declare one model's fleet policy body on the server."""
        return self.fleet(set_policy={model: spec})

    def flight(self, reason="manual_rpc", force=True):
        """Trigger a flight-recorder bundle on the server; returns the
        committed bundle path, or None while the recorder is disabled
        (server-side FLAGS.flight_dir unset)."""
        return self._call({"cmd": "flight", "reason": str(reason),
                           "force": bool(force)}).get("bundle")

    def metrics_text(self):
        """The server's unified Prometheus-style exposition."""
        return self._call({"cmd": "metrics"})["text"]

    def trace(self, trace_id=None, limit=2048, kind=None):
        """Span-ring readout: all spans of one trace_id, or the most
        recent `limit` (optionally filtered by kind)."""
        msg = {"cmd": "trace", "limit": int(limit)}
        if trace_id is not None:
            msg["trace_id"] = str(trace_id)
        if kind is not None:
            msg["kind"] = str(kind)
        return self._call(msg)

    def shutdown_server(self, drain=True):
        try:
            return self._call({"cmd": "shutdown", "drain": bool(drain)})
        except (ConnectionError, OSError, EOFError):
            return None

    def close(self):
        self._drop_conn()
