"""Host-side variable RPC: the parameter-server transport.

Reference analogue: paddle/fluid/operators/distributed/ — `RPCClient`
(rpc_client.h:32 AsyncSendVar/AsyncGetVar/AsyncSendBarrier/AsyncFetchBarrier)
and the gRPC `SendRecvService` (send_recv.proto.in:20 SendVariable/
GetVariable) with zero-copy LoDTensor serde (grpc_serde.cc), serving the
listen_and_serv event loop (listen_and_serv_op.cc:106 RunSyncLoop).

TPU redesign: the *dense* gradient path rides XLA collectives (psum over
ICI), so this transport exists for the parameter-server capability —
sparse/lookup-table workloads, async SGD, and the test strategy
(test_dist_base subprocess clusters). It is a length-prefixed TCP protocol
carrying numpy buffers (raw bytes + dtype/shape header — the zero-copy serde
analogue), stdlib-only so subprocess tests need no extra infra.

Sync-loop semantics (listen_and_serv_op.cc:106): trainers send grads then a
send-barrier; when `Fanin` barriers arrive the server averages each grad
slot, runs that param's optimize block, bumps the generation, and wakes Get
waiters; fetch-barrier closes the step.
"""

import os
import socket
import socketserver
import struct
import threading

import numpy as np

from ..native.wire import WireError, decode as _wire_decode, \
    encode as _wire_encode

__all__ = ["VariableServer", "RPCClient", "serialize_array",
           "deserialize_array"]

_HDR = struct.Struct("<Q")
# Frame cap: a hostile/garbled length prefix must not become an OOM.
# slice_variable keeps pserver blocks ~MBs, so 256 MiB leaves two
# orders of magnitude of headroom while keeping the worst case of a
# bogus header a bounded allocation; unsliced jumbo tensors can raise
# it via PADDLE_TPU_MAX_RPC_FRAME (bytes).
_MAX_FRAME = int(os.environ.get("PADDLE_TPU_MAX_RPC_FRAME", 1 << 28))


def _frame(obj):
    """Typed native wire frame (native/wire.cc) with a u64 length prefix —
    no pickle anywhere on the socket path (the reference's typed
    VariableMessage serde, grpc_serde.cc, not arbitrary object streams):
    the bytes of one message as they go on a socket."""
    payload = _wire_encode(obj)
    if len(payload) > _MAX_FRAME:
        # the peer's receive loop enforces the same cap; failing here
        # names the fix instead of leaving the peer to drop the socket
        raise WireError(
            "outgoing frame is %d bytes, above the %d-byte cap; export "
            "PADDLE_TPU_MAX_RPC_FRAME on both ends to raise it"
            % (len(payload), _MAX_FRAME))
    return _HDR.pack(len(payload)) + payload


def _send_msg(sock, obj):
    """Send one `_frame`; returns the bytes it put on the socket."""
    data = _frame(obj)
    sock.sendall(data)
    return len(data)


def _recv_exact(sock, n):
    buf = bytearray()
    while len(buf) < n:
        chunk = sock.recv(n - len(buf))
        if not chunk:
            raise ConnectionError("socket closed mid-message")
        buf.extend(chunk)
    return bytes(buf)


def _recv_msg(sock):
    (n,) = _HDR.unpack(_recv_exact(sock, _HDR.size))
    if n > _MAX_FRAME:
        raise WireError("wire frame length %d exceeds cap" % n)
    msg = _wire_decode(_recv_exact(sock, n))
    if not isinstance(msg, dict):
        # every protocol message (request or reply) is a dict — anything
        # else is malformed even when the frame itself decodes
        raise WireError("protocol message must be a dict, got %s"
                        % type(msg).__name__)
    return msg


def serialize_array(arr):
    """Normalize to a wire-encodable ndarray (the codec itself writes the
    dtype/shape header + raw buffer — grpc_serde.cc analogue)."""
    return np.ascontiguousarray(arr)


def deserialize_array(msg):
    return np.asarray(msg)


def wait_server_ready(endpoints, timeout=60.0, policy=None):
    """Block until every endpoint accepts TCP connections (reference
    transpiler/details/checkport.py:21 — trainers poll pserver ports
    instead of racing the server's bind).  The poll cadence is the
    shared jittered-backoff RetryPolicy (utils/retry.py), unbounded in
    attempts but bounded by `timeout`: many workers polling a restarting
    pserver must not stampede it in lockstep."""
    import time
    if policy is None:
        from ..utils.retry import default_rpc_policy
        policy = default_rpc_policy(max_attempts=1 << 30, max_delay=1.0)
    deadline = time.monotonic() + timeout
    pending = list(endpoints)
    delays = policy.delays()
    while pending:
        ep = pending[0]
        host, port = ep.rsplit(":", 1)
        try:
            s = socket.create_connection((host, int(port)), timeout=1.0)
            s.close()
            pending.pop(0)
            delays = policy.delays()  # fresh backoff per endpoint
        except OSError:
            if time.monotonic() > deadline:
                raise TimeoutError(
                    "server %s not ready within %.0fs" % (ep, timeout))
            policy.sleep(min(next(delays, 1.0),
                             max(deadline - time.monotonic(), 0.0)))


class VariableServer:
    """One pserver endpoint: a variable store + sync barrier loop.

    `optimize_fn(param_name, avg_grads_dict)` is supplied by the
    listen_and_serv op lowering; it runs that param's optimize sub-block
    against the server's store.
    """

    def __init__(self, endpoint, fanin=1, sync_mode=True, optimize_fn=None,
                 grad_to_param=None, pre_apply_fn=None, dc_asgd=False,
                 dc_lambda=0.04):
        host, port = endpoint.rsplit(":", 1)
        self._addr = (host, int(port))
        self.fanin = max(int(fanin), 1)
        self.sync_mode = sync_mode
        self.optimize_fn = optimize_fn
        self.pre_apply_fn = pre_apply_fn
        self.grad_to_param = dict(grad_to_param or {})
        # delay-compensated async SGD (reference request_handler_impl.cc
        # enable_dc_asgd + transpiler _append_dc_asgd_ops): per-trainer
        # param snapshots taken at Get time; on grad arrival the
        # correction g + λ·g⊙g⊙(w_now − w_snapshot) compensates the
        # trainer's staleness (Zheng et al., 2017)
        self.dc_asgd = bool(dc_asgd) and not sync_mode
        self.dc_lambda = float(dc_lambda)
        self._dc_params = frozenset(self.grad_to_param.values())
        self._param_bak = {}      # (trainer_id, param) -> np.ndarray
        self.store = {}           # name -> np.ndarray
        self._grad_buffers = {}   # grad name -> [np.ndarray]
        self._lock = threading.Condition()
        self._send_barriers = 0
        self._fetch_barriers = 0
        self._generation = 0
        self._trainers = {}       # trainer_id -> incarnation
        self._stopped = False
        self._server = None
        self._thread = None

    # ---- lifecycle ----
    def start(self, background=True):
        outer = self

        class Handler(socketserver.BaseRequestHandler):
            def handle(self):
                try:
                    while True:
                        msg = _recv_msg(self.request)
                        try:
                            reply = outer._dispatch(msg)
                        except (KeyError, TypeError, AttributeError,
                                ValueError) as e:
                            # a decodable frame with the wrong field shape
                            # gets an error reply, not a dead handler
                            reply = {"error": "bad request: %r" % (e,)}
                        if reply is _CLOSE:
                            _send_msg(self.request, {"ok": True})
                            break
                        if reply is not None:
                            try:
                                _send_msg(self.request, reply)
                            except WireError as e:
                                # outgoing frame over the cap (e.g. a Get
                                # of a pserver-initialized jumbo var): the
                                # stream is still in sync, so surface the
                                # actionable PADDLE_TPU_MAX_RPC_FRAME
                                # message to the client instead of
                                # silently dropping the connection
                                _send_msg(self.request,
                                          {"error": str(e)})
                except WireError:
                    # malformed INCOMING frame: the stream is desynced —
                    # drop the connection (never crash the server)
                    pass
                except (ConnectionError, EOFError):
                    pass

        class Server(socketserver.ThreadingTCPServer):
            allow_reuse_address = True
            daemon_threads = True

        self._server = Server(self._addr, Handler)
        self._addr = self._server.server_address
        if background:
            self._thread = threading.Thread(target=self._serve, daemon=True)
            self._thread.start()
        else:
            self._serve()
        return self

    @property
    def endpoint(self):
        return "%s:%d" % (self._addr[0], self._addr[1])

    def _serve(self):
        self._server.timeout = 0.2  # poll the stop flag between accepts
        with self._server:
            while not self._stopped:
                self._server.handle_request()

    def stop(self):
        with self._lock:
            self._stopped = True
            self._lock.notify_all()
        try:
            # unblock the accept loop
            s = socket.create_connection(self._addr, timeout=1)
            s.close()
        except OSError:
            pass

    # ---- request dispatch ----
    def _dispatch(self, msg):
        cmd = msg.get("cmd")
        if cmd == "send":
            return self._handle_send(msg)
        if cmd == "get":
            return self._handle_get(msg)
        if cmd == "send_barrier":
            return self._handle_send_barrier(msg)
        if cmd == "fetch_barrier":
            return self._handle_fetch_barrier(msg)
        if cmd == "put":  # direct store write (init / checkpoint restore)
            with self._lock:
                self.store[msg["name"]] = deserialize_array(msg["var"])
            return {"ok": True}
        if cmd == "prefetch":
            return self._handle_prefetch(msg)
        if cmd == "sparse_push":
            return self._handle_sparse_push(msg)
        if cmd == "checkpoint":
            return self._handle_checkpoint(msg)
        if cmd == "load_checkpoint":
            return self._handle_load_checkpoint(msg)
        if cmd == "register_trainer":
            return self._handle_register_trainer(msg)
        if cmd == "exit":
            self._stopped = True
            with self._lock:
                self._lock.notify_all()
            return _CLOSE
        return {"error": "unknown cmd %r" % cmd}

    def _handle_send(self, msg):
        name = msg["name"]
        arr = deserialize_array(msg["var"])
        with self._lock:
            if self.sync_mode:
                self._grad_buffers.setdefault(name, []).append(arr)
            else:
                # async SGD: apply immediately (RunAsyncLoop,
                # listen_and_serv_op.cc:216)
                self._apply_one(name, arr,
                                trainer_id=msg.get("trainer_id", 0))
                self._generation += 1
                self._lock.notify_all()
        return {"ok": True}

    def _handle_send_barrier(self, msg):
        with self._lock:
            self._send_barriers += 1
            if self._send_barriers >= self.fanin:
                self._apply_all()
                self._send_barriers = 0
                self._generation += 1
                self._lock.notify_all()
            else:
                gen = self._generation
                while self._generation == gen and not self._stopped:
                    self._lock.wait(timeout=30)
        return {"ok": True}

    def _handle_get(self, msg):
        name = msg["name"]
        gen = msg.get("generation", 0)
        with self._lock:
            if self.sync_mode:
                while self._generation < gen and not self._stopped:
                    self._lock.wait(timeout=30)
            val = self.store.get(name)
            if val is not None and self.dc_asgd and \
                    name in self._dc_params:
                # snapshot what this trainer is about to compute on
                # (reference RequestGetHandler '%s.trainer_%d_bak' copy);
                # only params a grad maps to can receive the correction
                tid = msg.get("trainer_id", 0)
                self._param_bak[(tid, name)] = np.array(val, copy=True)
        if val is None:
            return {"error": "no var %s" % name}
        return {"ok": True, "var": serialize_array(val),
                "generation": self._generation}

    def _handle_fetch_barrier(self, msg):
        with self._lock:
            self._fetch_barriers += 1
            if self._fetch_barriers >= self.fanin:
                self._fetch_barriers = 0
                self._lock.notify_all()
        return {"ok": True, "generation": self._generation}

    def _handle_prefetch(self, msg):
        """Distributed lookup-table remote prefetch (reference
        distributed_ops/prefetch_op.cc + lookup_sparse_table): the global
        table is row-sharded round-robin across pservers — global row id
        maps to shard `id % num_shards`, local row `id // num_shards`
        (transpiler ps_dispatcher.py RoundRobin semantics on ids). This
        server holds shard rows as a dense [ceil(V/ns), D] array."""
        name = msg["name"]
        ids = deserialize_array(msg["ids"]).reshape(-1).astype(np.int64)
        ns = max(int(msg.get("num_shards", 1)), 1)
        with self._lock:
            table = self.store.get(name)
            if table is None:
                return {"error": "no table %s" % name}
            rows = table[ids // ns].copy()
        return {"ok": True, "var": serialize_array(rows)}

    def _handle_sparse_push(self, msg):
        """Sparse-row gradient push: applies the update directly on this
        shard's rows (reference's pserver-side sparse optimize block for
        the distributed lookup table; plain SGD like lookup_sparse_table's
        default)."""
        name = msg["name"]
        ids = deserialize_array(msg["ids"]).reshape(-1).astype(np.int64)
        values = deserialize_array(msg["values"])
        lr = float(msg.get("lr", 1.0))
        ns = max(int(msg.get("num_shards", 1)), 1)
        with self._lock:
            table = self.store.get(name)
            if table is None:
                return {"error": "no table %s" % name}
            np.subtract.at(table, ids // ns, lr * values)
            self._generation += 1
        return {"ok": True}

    def _ckpt_path(self, dirname):
        import os
        return os.path.join(
            dirname, "pserver_%s.ckpt" % self.endpoint.replace(":", "_"))

    def _handle_checkpoint(self, msg):
        """checkpoint_notify (distributed_ops/checkpoint_notify_op.cc):
        persist this shard's store — params AND optimizer accumulators —
        with CRC32 + metadata (go/pserver/service.go:119 checkpointMeta,
        :145 parameterCheckpoint: etcd meta replaced by an in-file
        header; the write is atomic via os.replace)."""
        import os
        import time as _time
        import uuid
        from .elastic import save_state_snapshot
        dirname = msg["dirname"]
        os.makedirs(dirname, exist_ok=True)
        with self._lock:
            snap = {k: v.copy() for k, v in self.store.items()}
            gen = self._generation
        path = self._ckpt_path(dirname)
        save_state_snapshot(path, {
            "meta": {"uuid": uuid.uuid4().hex, "timestamp": _time.time(),
                     "endpoint": self.endpoint, "generation": gen},
            "store": snap,
        })
        return {"ok": True, "path": path}

    def load_checkpoint(self, dirname):
        """go/pserver/service.go:174 LoadCheckpoint: CRC-verify and
        restore this shard's store (raises ValueError on corruption)."""
        from .elastic import load_state_snapshot
        st = load_state_snapshot(self._ckpt_path(dirname))
        with self._lock:
            self.store.update(st["store"])
            self._generation = st["meta"].get("generation", 0)
        return st["meta"]

    def _handle_load_checkpoint(self, msg):
        try:
            meta = self.load_checkpoint(msg["dirname"])
        except (OSError, ValueError) as e:
            return {"error": str(e)}
        return {"ok": True, "meta": meta}

    def _handle_register_trainer(self, msg):
        """Trainer (re)join. A REJOIN — same trainer_id, new incarnation
        — means the previous incarnation died mid-step: reset the sync
        loop's partial state (pending grad buffers + barrier counts) so
        surviving trainers don't deadlock on the dead trainer's barrier
        (reference listen_and_serv_op.cc:172 NeedResetAllVars after
        trainer rejoin)."""
        tid = msg["trainer_id"]
        inc = msg.get("incarnation", 0)
        with self._lock:
            prev = self._trainers.get(tid)
            rejoin = prev is not None and inc > prev
            self._trainers[tid] = inc
            if rejoin:
                self._grad_buffers.clear()
                self._send_barriers = 0
                self._fetch_barriers = 0
                self._lock.notify_all()
        return {"ok": True, "rejoin": bool(rejoin),
                "generation": self._generation}

    # ---- optimize ----
    def _apply_all(self):
        if self.pre_apply_fn is not None:
            self.pre_apply_fn(self.store)
        grads = {}
        for gname, bufs in self._grad_buffers.items():
            if bufs:
                acc = bufs[0].astype(np.float64)
                for b in bufs[1:]:
                    acc = acc + b
                grads[gname] = (acc / len(bufs)).astype(bufs[0].dtype)
        self._grad_buffers.clear()
        for gname, avg in grads.items():
            self._apply_one(gname, avg)

    def _apply_one(self, grad_name, grad, trainer_id=None):
        pname = self.grad_to_param.get(grad_name)
        if self.dc_asgd and pname is not None and trainer_id is not None:
            w_now = self.store.get(pname)
            bak = self._param_bak.get((trainer_id, pname))
            if w_now is not None and bak is not None and \
                    np.shape(bak) == np.shape(grad):
                g = np.asarray(grad)
                grad = g + self.dc_lambda * g * g * \
                    (np.asarray(w_now) - bak)
        if self.optimize_fn is not None and pname is not None:
            self.optimize_fn(pname, grad_name, grad, self.store)
        elif pname is not None and pname in self.store:
            # no optimizer wired: plain SGD with lr=1 would be wrong; store
            # the grad so callers can inspect
            self.store["@GRAD//" + grad_name] = grad


_CLOSE = object()


class RPCClient:
    """reference rpc_client.h:32 (sync calls; the Async* naming kept for
    API recognizability — each call is a blocking round-trip on a pooled
    connection per endpoint)."""

    def __init__(self):
        # connections are THREAD-LOCAL: barrier calls block server-side until
        # all trainers arrive, so two trainer threads sharing one socket
        # would deadlock each other (one holds the connection while parked
        # in the barrier). One socket per (thread, endpoint) mirrors the
        # reference's per-trainer gRPC channels.
        self._tls = threading.local()

    def _conn(self, ep):
        conns = getattr(self._tls, "conns", None)
        if conns is None:
            conns = self._tls.conns = {}
        s = conns.get(ep)
        if s is None:
            host, port = ep.rsplit(":", 1)
            from ..flags import FLAGS
            s = socket.create_connection((host, int(port)),
                                         timeout=FLAGS.rpc_deadline)
            conns[ep] = s
        return s

    def _generation_map(self):
        gens = getattr(self._tls, "gens", None)
        if gens is None:
            gens = self._tls.gens = {}
        return gens

    def _call(self, ep, msg):
        from ..flags import FLAGS
        if getattr(FLAGS, "enable_rpc_profiler", False):
            from ..fluid.profiler import RecordEvent
            with RecordEvent("rpc/%s" % msg.get("cmd", "?")):
                return self._call_impl(ep, msg)
        return self._call_impl(ep, msg)

    # Commands safe to replay after a connection failure: pure reads and
    # absolute writes.  Barriers/sends mutate counters server-side — a
    # blind replay could double-count, so those surface the error.
    _IDEMPOTENT = frozenset(["get", "prefetch", "put", "load_checkpoint",
                             "checkpoint", "register_trainer"])

    def _call_impl(self, ep, msg):
        attempt_one = self._call_once
        if msg.get("cmd") in self._IDEMPOTENT:
            from ..utils.retry import default_rpc_policy

            def _drop_conn(exc, attempt):
                conns = getattr(self._tls, "conns", None)
                s = conns.pop(ep, None) if conns else None
                if s is not None:
                    try:
                        s.close()
                    except OSError:
                        pass

            return default_rpc_policy().call(
                lambda: attempt_one(ep, msg), on_retry=_drop_conn)
        return attempt_one(ep, msg)

    def _call_once(self, ep, msg):
        s = self._conn(ep)
        _send_msg(s, msg)
        reply = _recv_msg(s)
        if "error" in reply:
            raise RuntimeError("rpc %s -> %s: %s" % (msg.get("cmd"), ep,
                                                     reply["error"]))
        if "generation" in reply:
            self._generation_map()[ep] = reply["generation"]
        return reply

    def async_send_var(self, ep, name, value, trainer_id=0):
        return self._call(ep, {"cmd": "send", "name": name,
                               "trainer_id": int(trainer_id),
                               "var": serialize_array(np.asarray(value))})

    def async_get_var(self, ep, name, trainer_id=0):
        gen = self._generation_map().get(ep, 0)
        reply = self._call(ep, {"cmd": "get", "name": name,
                                "trainer_id": int(trainer_id),
                                "generation": gen})
        return deserialize_array(reply["var"])

    def async_send_barrier(self, ep):
        return self._call(ep, {"cmd": "send_barrier"})

    def async_fetch_barrier(self, ep):
        return self._call(ep, {"cmd": "fetch_barrier"})

    def put_var(self, ep, name, value):
        return self._call(ep, {"cmd": "put", "name": name,
                               "var": serialize_array(np.asarray(value))})

    def checkpoint_notify(self, ep, dirname):
        return self._call(ep, {"cmd": "checkpoint", "dirname": dirname})

    def prefetch(self, ep, name, ids, num_shards=1):
        reply = self._call(ep, {"cmd": "prefetch", "name": name,
                                "ids": serialize_array(np.asarray(ids)),
                                "num_shards": num_shards})
        return deserialize_array(reply["var"])

    def sparse_push(self, ep, name, ids, values, lr=1.0, num_shards=1):
        return self._call(ep, {"cmd": "sparse_push", "name": name,
                               "ids": serialize_array(np.asarray(ids)),
                               "values": serialize_array(
                                   np.asarray(values)),
                               "lr": lr, "num_shards": num_shards})

    def load_checkpoint_notify(self, ep, dirname):
        return self._call(ep, {"cmd": "load_checkpoint",
                               "dirname": dirname})

    def register_trainer(self, ep, trainer_id, incarnation=0):
        return self._call(ep, {"cmd": "register_trainer",
                               "trainer_id": trainer_id,
                               "incarnation": incarnation})

    def send_exit(self, ep):
        try:
            return self._call(ep, {"cmd": "exit"})
        except (ConnectionError, OSError):
            return None

    def close(self):
        conns = getattr(self._tls, "conns", None)
        if conns:
            for s in conns.values():
                try:
                    s.close()
                except OSError:
                    pass
            conns.clear()


_global_client = None


def global_client():
    global _global_client
    if _global_client is None:
        _global_client = RPCClient()
    return _global_client
