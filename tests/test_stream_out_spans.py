"""A token followed out of the lane (OBSERVABILITY.md "the decode lane,
where the chip waits"; PR 39): `serving/emit`'s children `serving/finish`
and `serving/slot_free`, and the ONE `serving/stream_out` span a request
that its handler thread folds its frames into, on a tiny decode model
through `InferenceServer` + `ServingClient.infer_stream`.

* every `serving/finish` lies inside its `serving/emit`, every
  `serving/slot_free` inside its `serving/finish`; `order` runs over a
  delivery's enders and `enders` counts them;
* one `serving/stream_out` a request: the frames and tokens the client
  counted, from the request's first put to its last send;
* tracing off: none of it lands, neither the delivery nor the handler
  loop reads the clock, and the streams are the same token for token;
* a client that dies mid-stream still lands its span, and its stream is
  cancelled as ever.

CPU-safe under JAX_PLATFORMS=cpu.
"""

import sys
import threading
import time

import pytest

from paddle_tpu.inference.decode import (STEP_WINDOW,
                                         build_tiny_decode_model)
from paddle_tpu.obs import tracing as obs_tracing
from paddle_tpu.serving import (InferenceServer, ServingClient,
                                set_dispatch_delay)

W = STEP_WINDOW
NEW = ("serving/finish", "serving/slot_free", "serving/stream_out")
# a wave of three that ride the same dispatches and end in ONE delivery
# (its chunks differ, so its frames do), then a request of one token,
# which ends in its prefill and outside any delivery
WAVE = [([5, 9, 3], 1 + 2 * W, 1), ([7, 2], 1 + 2 * W, 3),
        ([1, 2, 3, 4], 1 + 2 * W, 2 * W)]
LONE = ([11, 6, 8], 1, 1)


@pytest.fixture(scope="module")
def artifact(tmp_path_factory):
    return build_tiny_decode_model(
        str(tmp_path_factory.mktemp("stream_out") / "lm"), eos_id=-1,
        vocab_size=32, d_model=16, n_heads=2, n_layers=2, max_seq_len=64,
        seed=7)


@pytest.fixture(autouse=True)
def _restore():
    was = obs_tracing.enabled()
    yield
    set_dispatch_delay(0.0)
    obs_tracing.set_enabled(was)


class _Served(object):
    """One server with the artifact at three slots.  `together(requests)`
    streams them from as many clients at once and has the lane admit them
    in ONE pass (it is held until all are queued)."""

    def __init__(self, artifact):
        self.server = InferenceServer().start()
        self.cli = ServingClient(self.server.endpoint)
        self.cli.load_model("lm", artifact, decode_slots=3)
        reg = self.server.registry
        with reg._lock:
            self.batcher = reg._entry_locked("lm", None).batcher

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.cli.close()
        self.server.shutdown(drain=False, timeout=10.0)

    def together(self, requests):
        b, plain = self.batcher, self.batcher._admissible
        b._admissible = lambda lane: (len(b._pending) >= len(requests)
                                      and plain(lane))
        out = [None] * len(requests)

        def one(i, prompt, max_new, chunk):
            cli = ServingClient(self.server.endpoint)
            try:
                frames = list(cli.infer_stream(
                    "lm", prompt, max_new_tokens=max_new,
                    chunk_tokens=chunk))
                out[i] = {"frames": frames, "info": cli.last_stream_info,
                          "t_end": time.monotonic()}
            finally:
                cli.close()
        threads = [threading.Thread(target=one, args=(i,) + r)
                   for i, r in enumerate(requests)]
        try:
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
        finally:
            b._admissible = plain
        assert all(out), out
        return out


def _serve(artifact):
    """The wave, then the lone request: (results in order, every span)."""
    with _Served(artifact) as s:
        got = s.together(WAVE) + s.together([LONE])
    # a handler lands its span after its last send: the client has the
    # frame a moment before
    deadline = time.monotonic() + 10.0
    while time.monotonic() < deadline and obs_tracing.enabled() and len(
            obs_tracing.recent_spans(name="serving/stream_out")) < len(got):
        time.sleep(0.01)
    return got, obs_tracing.recent_spans()


@pytest.fixture(scope="module")
def traced(artifact):
    was = obs_tracing.enabled()
    obs_tracing.set_enabled(True)
    obs_tracing.clear()
    try:
        return _serve(artifact)
    finally:
        obs_tracing.set_enabled(was)


def _end(span):
    return span["t0"] + span["dur_ms"] * 1e-3


def _named(spans, name):
    return [s for s in spans if s["name"] == name]


def _inside(inner, outer, slack=1e-6):
    return outer["t0"] - slack <= inner["t0"] and \
        _end(inner) <= _end(outer) + slack


# ---------------------------------------------------------------------------
# (i) the lane thread: `serving/emit` and its children
# ---------------------------------------------------------------------------

def test_every_finish_lies_inside_its_emit_and_counts_its_place(traced):
    got, spans = traced
    emits = {s["attrs"]["round"]: s for s in _named(spans, "serving/emit")}
    finishes = _named(spans, "serving/finish")
    assert len(finishes) == len(got)
    in_delivery = [f for f in finishes if f["parent"] == "serving/emit"]
    by_round = {}
    for f in in_delivery:
        emit = emits[f["attrs"]["round"]]
        assert _inside(f, emit), (f, emit)
        assert f["attrs"]["enders"] == emit["attrs"]["enders"]
        assert f["attrs"]["replica"] == emit["attrs"]["replica"] == 0
        by_round.setdefault(f["attrs"]["round"], []).append(f)
    # the wave ended in ONE delivery: three enders, in slot order, each
    # beginning where the one before it ended or later
    (wave,) = by_round.values()
    assert [f["attrs"]["order"] for f in wave] == [0, 1, 2]
    assert {f["attrs"]["enders"] for f in wave} == {3}
    assert sorted(f["attrs"]["slot"] for f in wave) == [0, 1, 2]
    assert {f["attrs"]["reason"] for f in wave} == {"length"}
    for a, b in zip(wave, wave[1:]):
        assert _end(a) <= b["t0"] + 1e-6
    # every delivery says what it did: chunks handed out, requests ended
    for rnd, emit in emits.items():
        assert emit["attrs"]["enders"] == len(by_round.get(rnd, []))
        assert 0 <= emit["attrs"]["puts"] <= 3 - emit["attrs"]["enders"]
    assert sum(e["attrs"]["puts"] for e in emits.values()) > 0
    # the request of one token ends in its prefill: a finish of its own
    # delivery, under the lane's pass
    (lone,) = [f for f in finishes if f["parent"] != "serving/emit"]
    assert lone["parent"] == "serving/lane_iter"
    assert (lone["attrs"]["order"], lone["attrs"]["enders"]) == (0, 1)
    assert lone["trace_id"] == got[-1]["info"]["trace_id"]


def test_every_slot_free_lies_inside_its_finish(traced):
    got, spans = traced
    finishes = {f["trace_id"]: f for f in _named(spans, "serving/finish")}
    frees = _named(spans, "serving/slot_free")
    assert sorted(finishes) == sorted(r["info"]["trace_id"] for r in got)
    assert len(frees) == len(finishes)
    for free in frees:
        fin = finishes[free["trace_id"]]
        assert free["parent"] == "serving/finish"
        assert _inside(free, fin), (free, fin)
        assert free["attrs"]["slot"] == fin["attrs"]["slot"]
        assert free["attrs"]["round"] == fin["attrs"]["round"]


# ---------------------------------------------------------------------------
# (ii) the handler threads: one `serving/stream_out` a request
# ---------------------------------------------------------------------------

def test_one_stream_out_a_request_with_what_the_client_counted(traced):
    got, spans = traced
    outs = _named(spans, "serving/stream_out")
    assert sorted(s["trace_id"] for s in outs) == \
        sorted(r["info"]["trace_id"] for r in got)
    for want, r in zip(WAVE + [LONE], got):
        tid = r["info"]["trace_id"]
        (out,) = [s for s in outs if s["trace_id"] == tid]
        a = out["attrs"]
        assert out["parent"] == "serving/request" and a["replica"] == 0
        assert a["frames"] == len(r["frames"])
        assert a["tokens"] == r["info"]["new_tokens"] == want[1]
        assert a["bytes"] > 0
        for part in ("lane", "wake", "send"):
            assert a[part + "_ms_sum"] >= 0.0
        for part in ("wake", "send"):
            assert 0.0 <= a[part + "_ms_max"] <= a[part + "_ms_sum"] + 1e-9
            # a mean of the frames cannot pass the largest of them
            assert a[part + "_ms_sum"] <= \
                a[part + "_ms_max"] * a["frames"] + 1e-6
        # from the request's first put: at the prefill's end or after it
        # (chunk 1: the put of the first token, in `_prefill`), never
        # after the request's own end, where the last flush is ...
        (dec,) = [s for s in spans if s["name"] == "serving/decode"
                  and s["trace_id"] == tid]
        (req,) = [s for s in spans if s["name"] == "serving/request"
                  and s["trace_id"] == tid]
        assert dec["t0"] <= out["t0"] <= _end(req) + 1e-6
        # ... to its last send: the terminal frame leaves after the
        # request ended (the handler reads its clock about when the
        # client has the frame)
        assert _end(req) <= _end(out) + 1e-6
        assert _end(out) <= r["t_end"] + 1.0
        # what the parts cover lies inside the span
        assert a["send_ms_sum"] <= out["dur_ms"] + 1e-6
    # chunk 1: the prefill's token, then a frame a window; chunk 3: the
    # first window's put, then the flush; a chunk of two windows: the flush
    assert [[len(f) for f in r["frames"]] for r in got] == [
        [1, W, W], [1 + W, W], [1 + 2 * W], [1]]


def test_the_wire_and_the_events_are_what_they_were(artifact):
    """`events()` hands out (kind, payload) pairs and nothing else rides
    the queue; the lane's stamps travel beside it, one a chunk."""
    from paddle_tpu.inference.decode import GenerativePredictor
    from paddle_tpu.serving.batcher import DecodeBatcher
    obs_tracing.set_enabled(True)
    b = DecodeBatcher(GenerativePredictor(artifact), n_slots=1)
    try:
        s = b.submit([5, 9, 3], max_new_tokens=1 + W, chunk_tokens=1)
        events, stamps = [], []
        for ev in s.events(timeout=60):
            events.append(ev)
            if ev[0] == "tokens":
                stamps.append(s.take_stamps())
    finally:
        b.close()
    assert all(len(ev) == 2 for ev in events)
    assert [k for k, _ in events] == ["tokens", "tokens", "done"]
    assert [len(c) for _, c in events[:-1]] == [1, W]
    for made, put in stamps:
        assert made <= put
    assert s.take_stamps() is None


# ---------------------------------------------------------------------------
# (iii) tracing off
# ---------------------------------------------------------------------------

def test_tracing_off_lands_nothing_reads_no_clock_same_tokens(
        artifact, traced, monkeypatch):
    obs_tracing.set_enabled(False)
    obs_tracing.clear()
    calls = {}
    clock = time.monotonic

    def counted():
        name = sys._getframe(1).f_code.co_name
        calls[name] = calls.get(name, 0) + 1
        return clock()
    monkeypatch.setattr(time, "monotonic", counted)
    got, spans = _serve(artifact)
    monkeypatch.undo()
    assert not [s for s in spans if s["name"] in NEW + ("serving/emit",)]
    # the delivery, the handler loop and its record read no clock; a
    # finish reads it once, for the reply's timings, as it always did
    for name in ("_deliver", "_handle_infer_stream", "land"):
        assert name not in calls, (name, calls)
    assert calls["_finish"] == len(got)
    # an admission reads it twice: as the request is admitted, and for
    # its first token
    assert calls["_launch_prefill"] == len(got)
    assert calls["_land_prefill"] == len(got)
    on, _ = traced
    assert [r["frames"] for r in got] == [r["frames"] for r in on]
    assert [{k: v for k, v in r["info"].items() if k != "trace_id"}
            for r in got] == \
        [{k: v for k, v in r["info"].items() if k != "trace_id"}
         for r in on]


# ---------------------------------------------------------------------------
# (iv) a dead client
# ---------------------------------------------------------------------------

def test_a_dead_client_still_lands_its_span_and_is_cancelled(artifact):
    obs_tracing.set_enabled(True)
    obs_tracing.clear()
    with _Served(artifact) as s:
        set_dispatch_delay(0.02)
        victim = ServingClient(s.server.endpoint)
        it = victim.infer_stream("lm", [5, 9, 3], max_new_tokens=500,
                                 trace_id="dead-client", chunk_tokens=1)
        next(it)                # the stream is live
        it.close()              # the connection drops mid-stream
        victim.close()
        deadline = time.monotonic() + 20.0
        while time.monotonic() < deadline:
            done = obs_tracing.recent_spans(name="serving/stream_out") \
                and obs_tracing.recent_spans(name="serving/finish")
            if done:
                break
            time.sleep(0.01)
        set_dispatch_delay(0.0)
        assert s.batcher.slot_occupancy()[0] == 0
    spans = obs_tracing.recent_spans()
    (out,) = _named(spans, "serving/stream_out")
    (fin,) = _named(spans, "serving/finish")
    assert out["trace_id"] == fin["trace_id"] == "dead-client"
    # the lane dropped it at a dispatch boundary, as ever
    assert fin["attrs"]["reason"] == "cancelled"
    # the frames that went out before the send failed are counted, and
    # the span ends where it failed
    assert 1 <= out["attrs"]["frames"] <= out["attrs"]["tokens"] < 500
    assert out["t0"] <= _end(out)
