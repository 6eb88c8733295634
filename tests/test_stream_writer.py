"""One writer thread sends every stream's frames (SERVING.md "Streaming
wire protocol"; PR 45): `server._StreamWriter`, which the lanes wake once
or twice a delivery, on a tiny decode model through `InferenceServer` and
raw sockets that keep a frame's bytes.

* (a) sixteen concurrent `infer_stream` clients get, byte for byte, the
  frames `infer_stream` has always put on the wire around the tokens the
  in-process stream gives, in order, one terminal frame each, last; ONE
  thread ever puts a frame on a stream's socket;
* (b) a peer that stops reading holds up nobody else's frames, and past
  the bound of unsent frames it is cancelled as a dead client's, its
  slot freed within the chaos scenario `decode-disconnect`'s bound;
* (c) a peer that closes mid-stream: the same, and its
  `serving/stream_out` span still lands;
* (d) one `serving/write_pass` span an item: a delivery's puts, then its
  enders' flushes and terminal frames; tracing off lands nothing and the
  writer reads no clock;
* (d') a pass's `tokens` are those of the frames whose last byte it put on
  a socket, `tokens_total` their running sum (PR 54): over a run they sum
  to what the clients received, a frame a full socket held back counts in
  the pass that finishes it, the total advances with tracing off, and the
  `stats` reply and the Prometheus text carry it;
* (e) `shutdown(drain=True)` sends every queued terminal frame before the
  writer joins;
* (f) events put before the handler attached the stream arrive in order.

CPU-safe under JAX_PLATFORMS=cpu.
"""

import socket
import struct
import sys
import threading
import time

import numpy as np
import pytest

from paddle_tpu.distributed.rpc import _frame, _recv_exact, _send_msg
from paddle_tpu.inference.decode import (STEP_WINDOW, GenerativePredictor,
                                         build_tiny_decode_model)
from paddle_tpu.native.wire import decode as wire_decode
from paddle_tpu.obs import tracing as obs_tracing
from paddle_tpu.serving import (InferenceServer, ServingClient,
                                set_dispatch_delay)
from paddle_tpu.serving import server as server_mod
from paddle_tpu.serving.batcher import DecodeBatcher, DecodeStream

W = STEP_WINDOW
SLOTS = 4


@pytest.fixture(scope="module")
def artifact(tmp_path_factory):
    return build_tiny_decode_model(
        str(tmp_path_factory.mktemp("stream_writer") / "lm"), eos_id=-1,
        vocab_size=32, d_model=16, n_heads=2, n_layers=2, max_seq_len=256,
        seed=11)


@pytest.fixture(autouse=True)
def _restore():
    was = obs_tracing.enabled()
    yield
    set_dispatch_delay(0.0)
    obs_tracing.set_enabled(was)


class _Served(object):
    def __init__(self, artifact, **load):
        self.server = InferenceServer().start()
        self.cli = ServingClient(self.server.endpoint)
        self.cli.load_model("lm", artifact, decode_slots=SLOTS, **load)
        reg = self.server.registry
        with reg._lock:
            self.batcher = reg._entry_locked("lm", None).batcher
        self.lane = self.batcher._lanes[0]

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.cli.close()
        self.server.shutdown(drain=False, timeout=10.0)


def _request(prompt, max_new, chunk, trace_id, **more):
    return dict({"cmd": "infer_stream", "model": "lm",
                 "tokens": np.asarray(prompt, np.int32),
                 "max_new_tokens": max_new, "stream_chunk_tokens": chunk,
                 "trace_id": trace_id}, **more)


def _connect(endpoint):
    host, port = endpoint.rsplit(":", 1)
    return socket.create_connection((host, int(port)), timeout=60)


def _read_frame(sock):
    """(the frame's bytes as they came, the message)."""
    head = _recv_exact(sock, 8)
    body = _recv_exact(sock, struct.unpack("<Q", head)[0])
    return head + body, wire_decode(body)


def _raw_stream(endpoint, request):
    """Every frame of one request: [(bytes, message), ...]."""
    sock = _connect(endpoint)
    try:
        _send_msg(sock, request)
        frames = []
        while not frames or not frames[-1][1].get("done"):
            frames.append(_read_frame(sock))
        return frames
    finally:
        sock.close()


def _direct(artifact, requests):
    """The tokens of each (prompt, max_new) from an in-process stream
    that nobody attached, through its own queue."""
    b = DecodeBatcher(GenerativePredictor(artifact), n_slots=1)
    try:
        return [[t for chunk in b.submit(p, max_new_tokens=m,
                                         chunk_tokens=3) for t in chunk]
                for p, m in requests]
    finally:
        b.close()


def _wait(cond, seconds=20.0):
    deadline = time.monotonic() + seconds
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(0.005)
    return False


def _named(spans, name):
    return [s for s in spans if s["name"] == name]


# ---------------------------------------------------------------------------
# (a) the wire is what it was, and one thread writes it
# ---------------------------------------------------------------------------

def test_sixteen_clients_get_the_same_bytes_from_one_thread(
        artifact, monkeypatch):
    rng = np.random.RandomState(3)
    requests = [([int(x) for x in rng.randint(1, 32, size=1 + i % 5)],
                 1 + (5 * i) % 37, 1 + i % 4) for i in range(16)]
    want = _direct(artifact, [(p, m) for p, m, _ in requests])
    senders, framed = set(), []
    plain_some, plain_msg = server_mod._send_some, server_mod._send_msg

    def some(sock, data):
        senders.add(threading.get_ident())
        return plain_some(sock, data)

    def msg(sock, obj):
        framed.append(obj)
        return plain_msg(sock, obj)
    monkeypatch.setattr(server_mod, "_send_some", some)
    monkeypatch.setattr(server_mod, "_send_msg", msg)
    got = [None] * len(requests)
    with _Served(artifact) as s:
        def one(i, prompt, max_new, chunk):
            got[i] = _raw_stream(
                s.server.endpoint,
                _request(prompt, max_new, chunk, "wire-%d" % i,
                         debug=(i % 2 == 0)))
        threads = [threading.Thread(target=one, args=(i,) + r)
                   for i, r in enumerate(requests)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        writer = s.server._writer._thread.ident
    assert all(got), got
    for i, (frames, tokens) in enumerate(zip(got, want)):
        tid = "wire-%d" % i
        *chunks, (last_raw, last) = frames
        assert [t for _, m in chunks for t in m["tokens"]] == tokens
        for seq, (raw, m) in enumerate(chunks):
            assert m["seq"] == seq and "done" not in m
            # the bytes infer_stream has always sent for such a chunk
            assert raw == _frame({"chunk": True, "seq": seq,
                                  "tokens": [int(t) for t in m["tokens"]],
                                  "trace_id": tid})
        final = {"ok": True, "done": True, "trace_id": tid,
                 "finish_reason": "length", "new_tokens": len(tokens)}
        if i % 2 == 0:
            assert last["debug"]["trace_id"] == tid
            assert last["debug"]["tokens"] == len(tokens)
            final["debug"] = last["debug"]
        assert list(last) == list(final)
        assert last_raw == _frame(final)
    # one thread put every stream's frames on its socket; the handler
    # threads' `_send_msg` carried the one-shot verbs alone
    assert senders == {writer}
    assert not [m for m in framed if "chunk" in m or "done" in m]


# ---------------------------------------------------------------------------
# (b), (c) one slow or dead peer costs only its own stream
# ---------------------------------------------------------------------------

def _steps(s):
    return s.lane.steps


def test_a_peer_that_stops_reading_holds_up_nobody_and_is_cancelled(
        artifact):
    """The victim reads its first frame and then nothing, behind socket
    buffers of a few kilobytes: once they are full its frames wait with
    the writer, and past the bound it counts as dead."""
    obs_tracing.set_enabled(True)
    obs_tracing.clear()
    bound = server_mod._StreamWriter.MAX_UNSENT_FRAMES
    with _Served(artifact, fuse_steps=1) as s:
        # accepted connections inherit the listener's send buffer
        s.server._server.socket.setsockopt(
            socket.SOL_SOCKET, socket.SO_SNDBUF, 4608)
        s.batcher.max_new_cap = 250
        host, port = s.server.endpoint.rsplit(":", 1)
        victim = socket.socket()
        victim.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, 2304)
        victim.connect((host, int(port)))
        _send_msg(victim, _request([5, 9, 3], 250, 1, "stuck"))
        assert _read_frame(victim)[1]["seq"] == 0
        writer = s.server._writer
        assert _wait(lambda: len(writer._held) == 1)
        (out,) = writer._held
        # beside it: streams that begin and end while it is held up
        set_dispatch_delay(0.004)
        others = [_raw_stream(s.server.endpoint,
                              _request([7, 2], 12, 2, "beside-%d" % i))
                  for i in range(3)]
        assert all(f[-1][1]["finish_reason"] == "length" for f in others)
        assert not out.sent.is_set() and len(out.owed) <= bound
        # past the bound it counts as dead: cancelled, its slot freed at
        # the lane's next dispatch boundary, its connection dropped
        assert _wait(out.stream.cancelled)
        at_cancel = _steps(s)
        assert _wait(lambda: s.batcher.slot_occupancy()[0] == 0)
        assert _steps(s) - at_cancel <= 6
        assert isinstance(out.error, ConnectionError)
        assert "%d frames unsent" % (bound + 1) in str(out.error)
        assert len(out.stream.tokens) < 250
        assert not writer._held
        # what its buffers held comes through whole, then the end
        victim.settimeout(10.0)
        seqs = []
        with pytest.raises((ConnectionError, EOFError, OSError)):
            while True:
                seqs.append(_read_frame(victim)[1]["seq"])
        victim.close()
        assert seqs == list(range(1, 1 + len(seqs)))
        assert len(seqs) + 1 + bound + 1 <= len(out.stream.tokens)
        # the lane goes on: the slot serves the next request clean
        again = _raw_stream(s.server.endpoint,
                            _request([7, 2], 12, 2, "after"))
    assert [m["tokens"] for _, m in again[:-1]] == \
        [m["tokens"] for _, m in others[0][:-1]]
    spans = obs_tracing.recent_spans()
    passes = _named(spans, "serving/write_pass")
    # the others' frames went out in passes that left the victim's held
    assert [p for p in passes if p["attrs"]["backlogged"] == 1
            and p["attrs"]["frames"] > 0 and p["attrs"]["enders"] > 0]
    assert passes[-1]["attrs"]["backlogged"] == 0
    (fin,) = [f for f in _named(spans, "serving/finish")
              if f["trace_id"] == "stuck"]
    assert fin["attrs"]["reason"] == "cancelled"
    # its span counts the frames that went out whole
    (so,) = [o for o in _named(spans, "serving/stream_out")
             if o["trace_id"] == "stuck"]
    assert 1 <= so["attrs"]["frames"] < len(out.stream.tokens) - bound


def test_a_full_socket_that_drains_loses_nothing(artifact, monkeypatch):
    """A socket that takes a few bytes at a time: the frames wait with
    their stream, go out in order when it takes them, and nothing is
    cancelled."""
    plain = server_mod._send_some
    slow, calls = [], [0]

    def some(sock, data):
        if slow and sock.getpeername() == slow[0]:
            calls[0] += 1
            if calls[0] % 3:
                return 0
            data = bytes(data[:7])
        return plain(sock, data)
    monkeypatch.setattr(server_mod, "_send_some", some)
    (want,) = _direct(artifact, [([5, 9, 3], 20)])
    with _Served(artifact) as s:
        sock = _connect(s.server.endpoint)
        slow.append(sock.getsockname())
        _send_msg(sock, _request([5, 9, 3], 20, 2, "slow"))
        frames = []
        while not frames or not frames[-1][1].get("done"):
            frames.append(_read_frame(sock))
        # the connection is the handler's again: the next request on it
        _send_msg(sock, {"cmd": "health"})
        assert _read_frame(sock)[1]["ok"]
        sock.close()
    assert [t for _, m in frames[:-1] for t in m["tokens"]] == want
    assert [m["seq"] for _, m in frames[:-1]] == list(range(len(frames) - 1))
    assert frames[-1][1]["new_tokens"] == 20
    assert calls[0] > 3 * len(frames)


def test_a_peer_that_closes_mid_stream_is_cancelled_and_lands_its_span(
        artifact):
    obs_tracing.set_enabled(True)
    obs_tracing.clear()
    with _Served(artifact, fuse_steps=1) as s:
        set_dispatch_delay(0.01)
        victim = _connect(s.server.endpoint)
        _send_msg(victim, _request([5, 9, 3], 120, 1, "closed"))
        assert _read_frame(victim)[1]["seq"] == 0
        beside = threading.Thread(target=_raw_stream, args=(
            s.server.endpoint, _request([7, 2], 30, 1, "beside")))
        beside.start()
        at_close = _steps(s)
        victim.close()
        assert _wait(lambda: s.batcher.slot_occupancy()[0] <= 1
                     and obs_tracing.recent_spans(
                         name="serving/stream_out"))
        # a send or two notice the dead socket, the boundary after
        # reclaims the slot: the chaos scenario's bound
        assert _steps(s) - at_close <= 6
        beside.join(timeout=60)
        assert not beside.is_alive()
    spans = obs_tracing.recent_spans()
    outs = {o["trace_id"]: o for o in _named(spans, "serving/stream_out")}
    assert sorted(outs) == ["beside", "closed"]
    assert 1 <= outs["closed"]["attrs"]["frames"] < 120
    assert outs["beside"]["attrs"]["tokens"] == 30
    fins = {f["trace_id"]: f["attrs"]["reason"]
            for f in _named(spans, "serving/finish")}
    assert fins == {"closed": "cancelled", "beside": "length"}


# ---------------------------------------------------------------------------
# (d) `serving/write_pass`
# ---------------------------------------------------------------------------

def _wave(s, requests):
    """Stream `requests` at once, admitted in ONE pass of the lane."""
    b, plain = s.batcher, s.batcher._admissible
    b._admissible = lambda lane: (len(b._pending) >= len(requests)
                                  and plain(lane))
    got = [None] * len(requests)

    def one(i, req):
        got[i] = _raw_stream(s.server.endpoint, req)
    threads = [threading.Thread(target=one, args=(i, r))
               for i, r in enumerate(requests)]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        b._admissible = plain
    assert all(got), got
    return got


def test_one_write_pass_an_item_of_a_delivery(artifact):
    """Four streams ride the same dispatches and end in one delivery:
    every delivery's chunks go out in ONE pass, and the last one's
    enders (a flush and a terminal frame each) in one more."""
    obs_tracing.set_enabled(True)
    obs_tracing.clear()
    with _Served(artifact) as s:
        # so slow a device that the writer is done with an item before
        # the lane has the next one
        set_dispatch_delay(0.01)
        got = _wave(s, [_request([3 + i, 9], 2 + 3 * W, 1, "pass-%d" % i)
                        for i in range(SLOTS)])
        assert _wait(lambda: len(obs_tracing.recent_spans(
            name="serving/stream_out")) == SLOTS)
    spans = obs_tracing.recent_spans()
    emits = sorted(_named(spans, "serving/emit"), key=lambda e: e["t0"])
    passes = sorted(_named(spans, "serving/write_pass"),
                    key=lambda p: p["t0"])
    assert not any(p.get("parent") for p in passes)
    # the prefills' first tokens: a put each, an item each, and a pass
    # takes what is queued (a prefill of this model is microseconds)
    first = [p for p in passes if p["t0"] < emits[0]["t0"]]
    rest = passes[len(first):]
    assert sum(p["attrs"]["frames"] for p in first) == SLOTS
    assert all(p["attrs"]["frames"] == p["attrs"]["streams"]
               and p["attrs"]["enders"] == 0 for p in first)
    # then the deliveries, each its puts; the last its enders
    assert [(e["attrs"]["puts"], e["attrs"]["enders"]) for e in emits] == \
        [(SLOTS, 0)] * 3 + [(0, SLOTS)]
    assert [(p["attrs"]["frames"], p["attrs"]["streams"],
             p["attrs"]["enders"]) for p in rest] == \
        [(SLOTS, SLOTS, 0)] * 3 + [(SLOTS, SLOTS, SLOTS)]
    # a pass's frames are its deliveries' puts and enders' flushes
    assert sum(p["attrs"]["frames"] for p in rest) == \
        sum(e["attrs"]["puts"] + e["attrs"]["enders"] for e in emits)
    for p in passes:
        assert p["attrs"]["bytes"] > 0 and p["attrs"]["backlogged"] == 0
    assert sum(p["attrs"]["bytes"] for p in passes) == \
        sum(len(raw) for frames in got for raw, _ in frames)
    # a pass begins after its delivery's first put and is short
    for e, p in zip(emits, rest):
        assert e["t0"] <= p["t0"]
    for frames in got:
        assert [len(m["tokens"]) for _, m in frames[:-1]] == \
            [1, W, W, W, 1]


def _count_the_servers_clock_reads(monkeypatch):
    """{function of server.py: its reads of time.monotonic()} from here
    to `monkeypatch.undo()`."""
    readers = {}
    clock = time.monotonic

    def counted():
        code = sys._getframe(1).f_code
        if code.co_filename == server_mod.__file__:
            readers[code.co_name] = readers.get(code.co_name, 0) + 1
        return clock()
    monkeypatch.setattr(time, "monotonic", counted)
    return readers


def test_tracing_off_the_writer_lands_nothing_and_reads_no_clock(
        artifact, monkeypatch):
    obs_tracing.set_enabled(False)
    obs_tracing.clear()
    readers = _count_the_servers_clock_reads(monkeypatch)
    with _Served(artifact) as s:
        got = _wave(s, [_request([3 + i, 9], 2 + W, 1, "off-%d" % i)
                        for i in range(SLOTS)])
    monkeypatch.undo()
    assert all(f[-1][1]["new_tokens"] == 2 + W for f in got)
    assert not readers, readers
    assert not [s for s in obs_tracing.recent_spans() if s["name"] in (
        "serving/write_pass", "serving/stream_out")]


# ---------------------------------------------------------------------------
# (d') the writer counts the tokens it put on the wire
# ---------------------------------------------------------------------------

def _passes():
    return sorted(_named(obs_tracing.recent_spans(), "serving/write_pass"),
                  key=lambda p: p["t0"])


def _running_total_holds(passes):
    total = 0
    for p in passes:
        total += p["attrs"]["tokens"]
        assert p["attrs"]["tokens_total"] == total, passes
    return total


def test_the_passes_tokens_sum_to_what_the_clients_received(artifact):
    """Two waves of streams of several lengths and chunkings: the
    `tokens` of the run's passes sum to the tokens on the clients' side
    of the sockets, `tokens_total` is their running sum on the pass's
    own clock, and nothing is owed at the end."""
    obs_tracing.set_enabled(True)
    obs_tracing.clear()
    with _Served(artifact) as s:
        set_dispatch_delay(0.002)
        got = _wave(s, [_request([3 + i, 9], 2 + (i + 1) * W, 1 + i,
                                 "sum-%d" % i) for i in range(SLOTS)])
        got += _wave(s, [_request([7, 2 + i], 5 + i, 2, "sum2-%d" % i)
                         for i in range(SLOTS)])
        assert _wait(lambda: len(obs_tracing.recent_spans(
            name="serving/stream_out")) == 2 * SLOTS)
        writer = s.server._writer
        received = sum(len(m["tokens"]) for frames in got
                       for _, m in frames[:-1])
        assert received == sum(f[-1][1]["new_tokens"] for f in got)
        assert writer.tokens_sent == received
    passes = _passes()
    assert _running_total_holds(passes) == received
    assert all(p["attrs"]["unsent_bytes"] == 0 for p in passes)
    # a pass that sent chunk frames sent at least a token a frame
    assert all(p["attrs"]["tokens"] >= p["attrs"]["frames"]
               for p in passes)
    # each request's span counts its own tokens: the two agree
    assert sum(o["attrs"]["tokens"] for o in _named(
        obs_tracing.recent_spans(), "serving/stream_out")) == received


def _held_back():
    """A writer of its own, one stream on a socket pair whose sending
    side holds a few kilobytes, and the stream's reader (not started)."""
    writer = server_mod._StreamWriter().start()
    a, b = socket.socketpair()
    a.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 4608)
    stream = DecodeStream("held", 1, 9)
    frames = []

    def read():
        b.settimeout(30.0)
        while not frames or not frames[-1].get("done"):
            frames.append(_read_frame(b)[1])
    return writer, (a, b), stream, frames, threading.Thread(target=read)


def test_a_held_frame_counts_in_the_pass_that_finishes_it():
    """A frame larger than its socket's buffer, to a peer that does not
    read yet: the pass that encoded it sent none of its tokens, nor does
    the pass of the frame behind it; the pass in which `_retry` puts the
    last byte out counts them all."""
    obs_tracing.set_enabled(True)
    obs_tracing.clear()
    writer, (a, b), stream, frames, reader = _held_back()
    big = list(range(1, 8001))
    try:
        out = writer.attach(a, stream, "held", False)
        stream._put_tokens(big)
        assert _wait(lambda: len(out.owed) == 1)
        stream._put_tokens([7, 8])
        assert _wait(lambda: len(out.owed) == 2)
        assert writer.tokens_sent == 0 and out in writer._held
        first, second = _passes()
        for p in (first, second):
            assert (p["attrs"]["frames"], p["attrs"]["tokens"],
                    p["attrs"]["tokens_total"],
                    p["attrs"]["backlogged"]) == (1, 0, 0, 1)
        # what the held stream still owes: the big frame's rest, then
        # the whole of the frame behind it
        assert 0 < first["attrs"]["unsent_bytes"] < first["attrs"]["bytes"]
        assert second["attrs"]["unsent_bytes"] == \
            first["attrs"]["unsent_bytes"] + second["attrs"]["bytes"]
        reader.start()
        stream._finish("length", obs_info={"replica": 0})
        assert out.sent.wait(30.0) and out.error is None
        reader.join(timeout=30)
    finally:
        writer.stop()
        a.close()
        b.close()
    assert [list(f["tokens"]) for f in frames[:-1]] == [big, [7, 8]]
    assert writer.tokens_sent == len(big) + 2
    passes = _passes()
    assert _running_total_holds(passes) == len(big) + 2
    (finishing,) = [p for p in passes if p["attrs"]["tokens"]]
    # both frames' last bytes left in ONE pass, after the passes that
    # encoded them
    assert finishing["attrs"]["tokens"] == len(big) + 2
    assert finishing["t0"] > second["t0"]
    assert passes[-1]["attrs"]["unsent_bytes"] == 0
    assert passes[-1]["attrs"]["backlogged"] == 0
    # the stream's own span agrees
    (so,) = _named(obs_tracing.recent_spans(), "serving/stream_out")
    assert (so["attrs"]["frames"], so["attrs"]["tokens"]) == \
        (2, len(big) + 2)


def test_tracing_off_the_total_advances_and_the_pass_reads_no_clock(
        monkeypatch):
    """The held-back stream again with `FLAGS.trace` off: the same
    total, no span, and nothing of the writer's reads the clock."""
    obs_tracing.set_enabled(False)
    obs_tracing.clear()
    writer, (a, b), stream, frames, reader = _held_back()
    big = list(range(1, 8001))
    readers = _count_the_servers_clock_reads(monkeypatch)
    try:
        out = writer.attach(a, stream, "held", False)
        stream._put_tokens(big)
        stream._put_tokens([7, 8])
        stream._put_tokens([9])
        # tokens of a frame that never went out whole are never counted
        assert writer.tokens_sent == 0
        reader.start()
        stream._finish("length", obs_info={"replica": 0})
        assert out.sent.wait(30.0) and out.error is None
        reader.join(timeout=30)
    finally:
        monkeypatch.undo()
        writer.stop()
        a.close()
        b.close()
    assert sum(len(f["tokens"]) for f in frames[:-1]) == len(big) + 3
    assert writer.tokens_sent == len(big) + 3
    assert not readers, readers
    assert not obs_tracing.recent_spans()


def test_a_broken_streams_unsent_tokens_are_never_counted():
    """A peer that goes away with frames held: what had not gone out
    whole stays out of the total."""
    obs_tracing.set_enabled(True)
    obs_tracing.clear()
    writer, (a, b), stream, frames, reader = _held_back()
    try:
        out = writer.attach(a, stream, "held", False)
        stream._put_tokens([5])
        assert _wait(lambda: writer.tokens_sent == 1)
        stream._put_tokens(list(range(1, 8001)))
        assert _wait(lambda: len(out.owed) == 1)
        b.close()
        assert out.sent.wait(30.0)
        assert isinstance(out.error, OSError) and stream.cancelled()
    finally:
        writer.stop()
        a.close()
    assert writer.tokens_sent == 1
    passes = _passes()
    assert _running_total_holds(passes) == 1
    assert passes[-1]["attrs"]["unsent_bytes"] == 0


def test_the_stats_reply_and_the_metrics_text_carry_the_writers_total(
        artifact):
    """`tokens_sent_total` beside the lane's emitted tokens: equal once
    every frame is out, on the `stats` verb and as a Prometheus family,
    with tracing off as with it on."""
    obs_tracing.set_enabled(False)
    with _Served(artifact) as s:
        assert s.cli.stats()["stats"]["tokens_sent_total"] == 0
        frames = _raw_stream(s.server.endpoint,
                             _request([5, 9, 3], 3 + W, 2, "stats"))
        assert frames[-1][1]["new_tokens"] == 3 + W
        stats = s.cli.stats()["stats"]
        assert stats["tokens_sent_total"] == 3 + W
        assert stats["models"]["lm"]["decode_tokens"] == 3 + W
        text = s.cli.metrics_text()
    (line,) = [ln for ln in text.splitlines() if ln.startswith(
        "paddle_tpu_serving_tokens_sent_total")]
    # the registry sums the process's servers: this one's are in it
    assert int(line.split()[-1]) >= 3 + W
    assert "# TYPE paddle_tpu_serving_tokens_sent_total counter" in text


# ---------------------------------------------------------------------------
# (e) shutdown
# ---------------------------------------------------------------------------

def test_a_draining_shutdown_sends_every_terminal_frame(artifact):
    server = InferenceServer().start()
    cli = ServingClient(server.endpoint)
    cli.load_model("lm", artifact, decode_slots=SLOTS)
    set_dispatch_delay(0.01)
    socks = []
    for i in range(2 * SLOTS):          # half of them wait for a slot
        sock = _connect(server.endpoint)
        _send_msg(sock, _request([4 + i, 1], 1 + 4 * W, W, "drain-%d" % i))
        socks.append(sock)
    assert _wait(lambda: server.metrics.snapshot()["models"]["lm"].get(
        "decode_slots_busy", 0) == SLOTS)
    cli.close()
    server.shutdown(drain=True, timeout=60.0)
    # the writer has joined: every frame is on its socket already
    assert not server._writer._thread.is_alive()
    for i, sock in enumerate(socks):
        sock.settimeout(5.0)
        frames = []
        while not frames or not frames[-1][1].get("done"):
            frames.append(_read_frame(sock))
        assert frames[-1][1]["finish_reason"] == "length"
        assert frames[-1][1]["new_tokens"] == 1 + 4 * W
        assert sum(len(m["tokens"]) for _, m in frames[:-1]) == 1 + 4 * W
        sock.close()
    # a writer that has stopped takes no stream: the handler would drop
    # the connection
    stream = DecodeStream("late", 1, 1)
    out = server._writer.attach(None, stream, "late", False)
    assert out.sent.is_set() and isinstance(out.error, ConnectionError)
    assert stream.cancelled()


# ---------------------------------------------------------------------------
# (f) attach
# ---------------------------------------------------------------------------

class _Sink(object):
    def __init__(self):
        self.items = []

    def post(self, events):
        self.items.append(list(events))


def test_events_put_before_the_attach_arrive_first_and_in_order():
    stream, sink = DecodeStream("t", 3, 9), _Sink()
    stream._put_tokens([1], (0.5, 1.0))
    stream._put_tokens([2, 3], (1.5, 2.0))
    stream.attach(sink, "tag")
    stream._put_tokens([4], (2.5, 3.0))
    stream._finish("length", obs_info={"replica": 0})
    assert sink.items == [
        [("tag", "tokens", [1], (0.5, 1.0)),
         ("tag", "tokens", [2, 3], (1.5, 2.0))],
        [("tag", "tokens", [4], (2.5, 3.0))],
        [("tag", "done", "length", None)]]
    # its own queue is empty from the attach on, and it is what it was
    # for everybody else
    assert stream._q.empty() and stream.take_stamps() is None
    assert stream.tokens == [1, 2, 3, 4] and stream.done()
    assert stream.result(timeout=1)[0].tolist() == [1, 2, 3, 4]


def test_a_stream_that_ended_before_the_attach_is_sent_whole(artifact):
    """The lane is done with a one-token request before its handler has
    attached it, or after: the frames are the same."""
    with _Served(artifact) as s:
        plain = s.server._writer.attach

        def late(sock, stream, trace_id, debug):
            assert _wait(stream.done)
            assert not stream._q.empty()
            return plain(sock, stream, trace_id, debug)
        s.server._writer.attach = late
        frames = _raw_stream(s.server.endpoint,
                             _request([5, 9, 3], 1 + W, 1, "late"))
    assert [m.get("seq") for _, m in frames] == [0, 1, None]
    assert [len(m["tokens"]) for _, m in frames[:-1]] == [1, W]
    assert frames[-1][1]["new_tokens"] == 1 + W


def test_one_item_joins_the_puts_of_a_block_and_of_its_inner_blocks():
    from paddle_tpu.serving.batcher import _one_item
    a, b, free = DecodeStream("a", 1, 9), DecodeStream("b", 1, 9), \
        DecodeStream("free", 1, 9)
    sink = _Sink()
    a.attach(sink, "A")
    b.attach(sink, "B")
    with _one_item():
        a._put_tokens([1])
        free._put_tokens([7])
        assert free._q.qsize() == 1         # nobody took it: at once
        with _one_item():
            b._put_tokens([2])
            a._finish("eos")
        assert not sink.items               # the inner block joined
    a_done = ("A", "done", "eos", None)
    assert sink.items == [[("A", "tokens", [1], None),
                           ("B", "tokens", [2], None), a_done]]
    b._fail(ValueError("x"))                # outside a block: an item
    assert [len(i) for i in sink.items] == [3, 1]


# ---------------------------------------------------------------------------
# many lanes, one writer, attaches at any moment
# ---------------------------------------------------------------------------

def test_many_threads_put_while_streams_are_attached_nothing_is_lost():
    """Twenty-four producer threads (lanes) put chunks on their streams
    with and without `_one_item` while the streams are attached, one
    after the other, to ONE writer: every stream's frames arrive in
    order, whole, one terminal frame last, whatever the interleaving."""
    from paddle_tpu.serving.batcher import _one_item
    n_streams, n_chunks = 24, 120
    writer = server_mod._StreamWriter().start()
    pairs = [socket.socketpair() for _ in range(n_streams)]
    streams = [DecodeStream("s%d" % i, 1, n_chunks)
               for i in range(n_streams)]
    outs, got = [None] * n_streams, [None] * n_streams
    go = threading.Event()

    def produce(i):
        go.wait()
        for k in range(0, n_chunks, 3):
            with _one_item():
                for j in range(k, k + 3):
                    streams[i]._put_tokens([i, j])
        streams[i]._finish("length", obs_info={"replica": 0})

    def attach():
        go.wait()
        for i in range(n_streams):
            outs[i] = writer.attach(pairs[i][0], streams[i], "s%d" % i,
                                    False)

    def read(i):
        pairs[i][1].settimeout(30.0)
        frames = []
        while not frames or not frames[-1].get("done"):
            frames.append(_read_frame(pairs[i][1])[1])
        got[i] = frames
    threads = [threading.Thread(target=produce, args=(i,))
               for i in range(n_streams)]
    threads += [threading.Thread(target=read, args=(i,))
                for i in range(n_streams)]
    threads.append(threading.Thread(target=attach))
    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        go.set()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
        assert all(o.sent.wait(10.0) and o.error is None for o in outs)
    finally:
        sys.setswitchinterval(was)
        writer.stop()
        for a, b in pairs:
            a.close()
            b.close()
    assert not writer._thread.is_alive() and not writer._taken
    for i, frames in enumerate(got):
        *chunks, last = frames
        assert [f["seq"] for f in chunks] == list(range(n_chunks))
        assert [list(f["tokens"]) for f in chunks] == \
            [[i, j] for j in range(n_chunks)]
        assert last["finish_reason"] == "length"
        assert last["new_tokens"] == 2 * n_chunks
