"""A decode lane's dispatch is a WINDOW of steps (SERVING.md "Fused
multi-step decode"; PR 30), held here to the lane pinned to one step a
dispatch, through the path a deployment uses: `InferenceServer` +
`ServingClient.infer_stream`, on a GPT-2-shaped and an OLMoE-shaped tiny
block.

* streams under windows are those of one-trip dispatches token for token
  and frame fact for frame fact (`finish_reason`, `new_tokens`): a stream
  that hits EOS mid-window, one whose budget ends on a window's last and on
  its first trip, one cancelled mid-window, a join while a window runs, a
  deadline inside a window; and, since a window runs on while ANY slot is
  alive (PR 43), a slot whose budget, EOS or cache room ends mid-window
  while its neighbour runs the window out;
* after `ModelEntry.warm` a lane that runs windows of 1, 3 and
  `STEP_WINDOW` trips lowers and compiles NOTHING: one step executable a
  slot count, the trips a runtime argument of it.

CPU-safe under JAX_PLATFORMS=cpu.
"""

import threading
import time

import numpy as np
import pytest

from paddle_tpu.flags import set_flags
from paddle_tpu.inference.decode import (STEP_WINDOW, GenerativePredictor,
                                         build_tiny_decode_model,
                                         greedy_decode)
from paddle_tpu.obs import tracing as obs_tracing
from paddle_tpu.serving import (DeadlineExceeded, InferenceServer,
                                ServingClient, set_dispatch_delay)

W = STEP_WINDOW
BLOCKS = {
    "gpt2": dict(vocab_size=32, d_model=16, n_heads=2, n_layers=2,
                 max_seq_len=128, seed=7),
    "olmoe": dict(vocab_size=97, d_model=64, n_heads=4, n_layers=2,
                  max_seq_len=128, seed=11, prefill_buckets=[16, 32],
                  block={"norm": "rmsnorm", "norm_eps": 1e-5,
                         "position": "rope", "rope_theta": 10000.0,
                         "qk_norm": True, "ffn": "moe_swiglu",
                         "n_experts": 8, "experts_per_token": 2,
                         "expert_width": 32, "norm_topk_prob": False}),
}
PROMPTS = ([5, 9, 3], [7, 2], [1, 2, 3, 4], [11, 6, 8, 2, 9])


@pytest.fixture(autouse=True)
def _quiet():
    was = obs_tracing.enabled()
    yield
    set_dispatch_delay(0.0)
    set_flags({"trace": was})


@pytest.fixture(scope="module", params=sorted(BLOCKS))
def models(request, tmp_path_factory):
    """(artifact whose streams end by length alone, artifact of the same
    weights whose EOS is a token that FIRST occurs mid-window in the
    greedy stream of `prompt`, prompt, that token's index in the stream)."""
    cfg = BLOCKS[request.param]
    root = tmp_path_factory.mktemp("window_" + request.param)
    endless = build_tiny_decode_model(str(root / "endless"), eos_id=-1,
                                      **cfg)
    pred = GenerativePredictor(endless)
    for prompt in PROMPTS:
        probe = greedy_decode(pred, prompt, 3 * W)[0]
        # decode token j is trip (j - 1) % W of its window on a one-slot
        # lane (token 0 is the prefill's): neither the first nor the last
        mid = [j for j in range(2, len(probe))
               if probe[j] not in probe[:j] and 0 < (j - 1) % W < W - 1]
        if mid:
            eos_at = mid[0]
            with_eos = build_tiny_decode_model(
                str(root / "eos"), eos_id=int(probe[eos_at]), **cfg)
            return endless, with_eos, prompt, eos_at
    raise AssertionError("no prompt's greedy stream has a fresh token "
                         "mid-window: %r" % (cfg,))


class _Served(object):
    """One server with `artifact` loaded at `slots` decode slots, its
    lane's window capped at `cap` (None: the built-in window)."""

    def __init__(self, artifact, slots, cap):
        self.server = InferenceServer().start()
        self.cli = ServingClient(self.server.endpoint)
        self.cli.load_model("lm", artifact, decode_slots=slots,
                            fuse_steps=cap)
        reg = self.server.registry
        with reg._lock:
            self.entry = reg._entry_locked("lm", None)
        self.batcher = self.entry.batcher

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.cli.close()
        self.server.shutdown(drain=False, timeout=10.0)

    def stream(self, prompt, max_new, **kw):
        """(tokens, finish_reason, new_tokens) of one stream."""
        cli = ServingClient(self.server.endpoint)
        try:
            toks = [t for c in cli.infer_stream(
                "lm", prompt, max_new_tokens=max_new, **kw) for t in c]
            info = cli.last_stream_info
            return toks, info["finish_reason"], info["new_tokens"]
        finally:
            cli.close()

    def together(self, requests):
        """`requests` [(prompt, max_new)] from as many clients at once;
        their results in order."""
        out = [None] * len(requests)

        def one(i, prompt, max_new):
            out[i] = self.stream(prompt, max_new)
        threads = [threading.Thread(target=one, args=(i, p, m))
                   for i, (p, m) in enumerate(requests)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        return out


def _step_attr(name):
    """Attribute `name` of the lane's dispatches (`serving/decode_step`),
    in dispatch order."""
    steps = sorted(obs_tracing.recent_spans(name="serving/decode_step"),
                   key=lambda s: s["attrs"]["round"])
    return [s["attrs"][name] for s in steps]


def _trips():
    return _step_attr("trips")


def _traced():
    set_flags({"trace": True})
    obs_tracing.clear()


CASES = ["eos_mid_window", "budget_ends_on_last_trip",
         "budget_ends_on_first_trip", "cancel_mid_window",
         "join_while_a_window_runs", "deadline_inside_a_window",
         "budgets_3_and_2W", "eos_in_one_slot_the_other_runs_on",
         "cache_room_ends_mid_window"]


@pytest.mark.parametrize("case", CASES)
def test_streams_under_windows_equal_one_trip_dispatches(models, case):
    endless, with_eos, prompt, eos_at = models
    run = globals()["_case_" + case]
    got = {}
    for cap in (None, 1):
        _traced()
        got[cap] = run(cap, endless, with_eos, prompt, eos_at)
        set_dispatch_delay(0.0)
    assert got[None] == got[1], case


def _case_eos_mid_window(cap, endless, with_eos, prompt, eos_at):
    """One slot, so every dispatch is a window: the slot stops in-graph
    with the trip in which EOS lands, and with no slot left alive the
    window ends there; the terminal frame counts the tokens that reached
    the client."""
    with _Served(with_eos, 1, cap) as s:
        toks, reason, n = s.stream(prompt, 4 * W)
    assert (reason, n, len(toks)) == ("eos", eos_at + 1, eos_at + 1)
    full, last = divmod(eos_at, W)              # eos_at decode tokens
    assert _trips() == ([W] * full + [last] if cap is None
                        else [1] * eos_at)
    return toks, reason, n


def _case_budget_ends_on_last_trip(cap, endless, *_):
    with _Served(endless, 1, cap) as s:
        out = s.stream(PROMPTS[0], 1 + 2 * W)   # the prefill's, 2 windows
    assert out[1:] == ("length", 1 + 2 * W)
    assert _trips() == ([W, W] if cap is None else [1] * 2 * W)
    return out


def _case_budget_ends_on_first_trip(cap, endless, *_):
    """The lane asks for its largest live budget: one slot with one token
    left gets a window of one trip."""
    with _Served(endless, 1, cap) as s:
        out = s.stream(PROMPTS[0], 2 + 2 * W)
    assert out[1:] == ("length", 2 + 2 * W)
    assert _trips() == ([W, W, 1] if cap is None else [1] * (2 * W + 1))
    return out


def _case_cancel_mid_window(cap, endless, *_):
    """The client goes away while a window runs: the slot is freed at
    that window's end, what had arrived is the stream's own prefix, and
    the next stream in that slot is served from clean rows."""
    set_dispatch_delay(0.01)                    # a window lasts ~80 ms
    with _Served(endless, 1, cap) as s:
        whole = s.stream(PROMPTS[0], 6 * W)[0]
        cli = ServingClient(s.server.endpoint)
        it = cli.infer_stream("lm", PROMPTS[0], max_new_tokens=6 * W)
        seen = []
        for chunk in it:
            seen += chunk
            if len(seen) > W + 1:               # inside the second window
                break
        it.close()
        cli.close()
        deadline = time.monotonic() + 30
        while s.batcher.slot_occupancy()[0] and time.monotonic() < deadline:
            time.sleep(0.005)
        assert s.batcher.slot_occupancy()[0] == 0
        assert seen == whole[:len(seen)]
        after = s.stream(PROMPTS[1], 5)
    return whole, after


def _case_join_while_a_window_runs(cap, endless, *_):
    """Two slots, three streams: the third arrives while the full lane
    runs a window and is admitted at the end of the window in which the
    first slot ends.  While two streams are live every window is full:
    the shorter one's end does not cut it."""
    set_dispatch_delay(0.01)
    with _Served(endless, 2, cap) as s:
        first = [None, None]

        def two():
            first[:] = s.together([(PROMPTS[0], 5 * W), (PROMPTS[1], 2 * W)])
        t = threading.Thread(target=two)
        t.start()
        deadline = time.monotonic() + 30
        while s.batcher.slot_occupancy()[0] < 2 \
                and time.monotonic() < deadline:
            time.sleep(0.002)
        third = s.stream(PROMPTS[2], W + 3)     # queues behind a full lane
        t.join(timeout=120)
    if cap is None:
        # 5W - 1, 2W - 1 and W + 2 decode tokens: only the dispatch in
        # which the LAST live stream ends is short
        assert set(_trips()[:-1]) == {W}, _trips()
    else:
        assert set(_trips()) == {1}
    return first, third


def _case_deadline_inside_a_window(cap, endless, *_):
    """A deadline that falls inside a window: the typed error at the
    window's end (the governor shortens the window that would cross it),
    and what had arrived is the stream's own prefix."""
    with _Served(endless, 1, cap) as s:
        whole = s.stream(PROMPTS[0], 100)[0]    # warms the lane's EWMA too
        set_dispatch_delay(0.02)                # 100 tokens need 2 s
        cli = ServingClient(s.server.endpoint)
        seen = []
        t0 = time.monotonic()
        with pytest.raises(DeadlineExceeded):
            for chunk in cli.infer_stream("lm", PROMPTS[0],
                                          max_new_tokens=100,
                                          deadline_ms=600.0):
                seen += chunk
        late = time.monotonic() - t0 - 0.6
        cli.close()
        assert 0 < len(seen) < 100 and seen == whole[:len(seen)]
        # within about one dispatch (8 x 20 ms) of the deadline, with
        # room for a loaded host; the whole stream would end 1.4 s late
        assert late < 1.0, late
        after = s.stream(PROMPTS[1], 5)
    return whole, after


def _ride_together(s, requests):
    """`requests` [(prompt, max_new)] admitted by ONE pass of the lane of
    `s`, the i-th into slot i: ([(tokens, finish_reason)], [each
    dispatch's (trips, tokens a slot)])."""
    sess = s.batcher._lanes[0].session
    fetch, seen = sess.fetch_fused, []

    def fetch_fused():
        toks, counts, trips = fetch()
        seen.append((trips, counts.tolist()))
        return toks, counts, trips
    sess.fetch_fused = fetch_fused
    with s.batcher._cv:
        streams = [s.batcher.submit(p, max_new_tokens=m)
                   for p, m in requests]
    outs = [(st.result(timeout=120)[0].tolist(), st.finish_reason)
            for st in streams]
    return outs, seen


def _windows(lefts, cap):
    """The dispatches of streams that ride together with `lefts` decode
    tokens to go each: a dispatch runs min(cap, the LARGEST of them)
    trips, and a slot its own min(cap, left) of those."""
    lefts, out = list(lefts), []
    while any(lefts):
        counts = [min(n, cap) for n in lefts]
        out.append((max(counts), counts))
        lefts = [n - c for n, c in zip(lefts, counts)]
    return out


def _case_budgets_3_and_2W(cap, endless, *_):
    """Two slots with 3 and 2W decode tokens to go: the first stops at
    trip 3 of a window that runs on for its neighbour, and the slot is
    empty in the next."""
    with _Served(endless, 2, cap) as s:
        outs, seen = _ride_together(
            s, [(PROMPTS[0], 1 + 3), (PROMPTS[1], 1 + 2 * W)])
    assert [o[1] for o in outs] == ["length", "length"]
    assert [len(o[0]) for o in outs] == [1 + 3, 1 + 2 * W]
    if cap is None:
        assert seen == [(W, [3, W]), (W, [0, W])]
    assert seen == _windows([3, 2 * W], cap or W)
    return outs


def _case_eos_in_one_slot_the_other_runs_on(cap, endless, with_eos, prompt,
                                            eos_at):
    """EOS lands in one slot mid-window while its neighbour runs on: the
    window is not cut, the slot's count is its own, and its EOS is the
    last token its stream gets."""
    pred = GenerativePredictor(with_eos)
    other = next(p for p in PROMPTS if p != prompt)
    requests = [(prompt, 4 * W), (other, 1 + 4 * W)]
    refs = [greedy_decode(pred, p, m) for p, m in requests]
    assert refs[0][1] == "eos" and len(refs[0][0]) == eos_at + 1
    with _Served(with_eos, 2, cap) as s:
        outs, seen = _ride_together(s, requests)
    assert outs == [(list(r[0]), r[1]) for r in refs]
    assert seen == _windows([len(r[0]) - 1 for r in refs], cap or W)
    if cap is None:
        # the window in which the first EOS lands is not cut by it
        # (unless both streams end in that very window)
        assert any(0 < min(c) < trips for trips, c in seen) \
            or len(seen) == 1, seen
    return outs


def _case_cache_room_ends_mid_window(cap, endless, *_):
    """A slot's cache fills at trip 3 of a window (13 + 14W + 3
    positions of 128) while its neighbour has budget and room left: the
    slot stops in-graph at its last position, writes no row past it, and
    the neighbour's window is whole."""
    assert 13 + 14 * W + 3 == 128
    with _Served(endless, 2, cap) as s:
        outs, seen = _ride_together(
            s, [(list(range(1, 14)), 400), (PROMPTS[1], 1 + 15 * W)])
    pred = GenerativePredictor(endless)
    assert outs[0] == (list(greedy_decode(pred, list(range(1, 14)),
                                          400)[0]), "length")
    assert [len(o[0]) for o in outs] == [1 + 14 * W + 3, 1 + 15 * W]
    assert seen == _windows([14 * W + 3, 15 * W], cap or W)
    if cap is None:
        assert seen[-1] == (W, [3, W])
    return outs


def a_slot_that_stops_sits_out_the_window(pred, prompts, j):
    """Two slots of `pred` through ONE window in which slot 0's budget
    ends at trip `j` while slot 1 runs all W, against a session that makes
    the same trips as one-trip dispatches (slot 0 held but not run from
    trip j on).  After the window every table of the slot state, of
    whatever kind (K/V or latent rows, conv windows, scanned states), is
    bit for bit that of the one-trip session: the stopped slot's is its
    state at its own stop, its neighbour's is unmoved by the stop; lengths,
    last tokens, routing facts and the streams that follow agree too; and
    `_kv_stream` counts the stopped slot's blocks over its own j trips.
    Shared by the suite of each kind of stack."""
    assert 0 < j < W and len(prompts) == 2
    win, one = pred.new_session(2), pred.new_session(2)
    for sess in (win, one):
        for slot, p in enumerate(prompts):
            sess.prefill(slot, p)
    at_launch = win.lengths.copy()
    # the host's count of what the kernel stages, at a block edge of 4
    # positions: ceil((length + t + 1) / 4) blocks a layer for a slot's
    # own trip t, one a layer for a trip it sits out
    layers, _, S = win._kc.shape[:3]
    keep, win._kv_block = win._kv_block, 4
    staged = win._kv_stream(np.asarray([j, W], np.int32), W)
    win._kv_block = keep
    own = [sum(-(-(int(n) + t + 1) // 4) for t in range(ran))
           for n, ran in zip(at_launch, (j, W))]
    assert staged == {"kv_blocks_live": layers * (sum(own) + (W - j)),
                      "kv_blocks_total": W * 2 * layers * (S // 4)}
    toks, counts, trips = win.decode_fused(W, budget=[j, W])
    assert (trips, counts.tolist()) == (W, [j, W])
    singles, facts = [], []
    for t in range(W):
        if t == j:
            one.active[0] = False            # holds its state, runs no more
        singles.append(one.decode())
        facts.append(one.last_routing)
    one.active[0] = True
    singles = np.stack(singles, axis=1)
    np.testing.assert_array_equal(toks[0, :j], singles[0, :j])
    np.testing.assert_array_equal(toks[1], singles[1])
    assert not toks[0, j:].any()
    for a, b in zip(win._tables(), one._tables()):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert np.asarray(a)[:, 0].any() and np.asarray(a)[:, 1].any()
    assert win.lengths.tolist() == one.lengths.tolist() \
        == (at_launch + [j, W]).tolist()
    assert win.last_tokens.tolist() == one.last_tokens.tolist()
    if win.last_routing is not None:
        # a slot that sits a trip out is not among the tokens it counts
        facts = np.stack(facts)                             # [W, L, 2]
        assert win.last_routing.tolist() == np.stack(
            [facts[:, :, 0].sum(axis=0), facts[:, :, 1].max(axis=0)],
            axis=1).tolist()
    # both slots go on as if nothing had stopped
    np.testing.assert_array_equal(win.decode_fused(3)[0],
                                  one.decode_fused(3)[0])


@pytest.mark.parametrize("j", [1, 3, W - 1])
def test_a_slot_that_stops_mid_window_keeps_its_rows(models, j):
    a_slot_that_stops_sits_out_the_window(
        GenerativePredictor(models[0]), [PROMPTS[3], PROMPTS[0]], j)


def test_a_warm_lane_compiles_nothing_whatever_the_window(models):
    """One step executable a slot count: after the load's warm-up, windows
    of 1, 3 and STEP_WINDOW trips (and the prefills, releases and the
    early end of a window between them) lower nothing, compile nothing and
    fetch nothing from jax's persistent cache: the events
    `benchmark/run.py::CompileWatch` counts inside a cell's window.  The
    prompts come in the order second bucket, first, second: a lane's first
    admission and its later ones must find the same executables."""
    import jax.monitoring
    from benchmark.run import CompileWatch
    endless, with_eos, prompt, eos_at = models
    events, on = [], [False]

    def listen(name, secs, **kw):
        if on[0] and name.startswith(CompileWatch.WATCHED):
            events.append(name)
    jax.monitoring.register_event_duration_secs_listener(listen)
    full, last = divmod(eos_at, W)
    long_prompt = list(range(1, 21))
    try:
        for artifact, requests, want in (
                (endless, ((long_prompt, 1 + W), (prompt, 1 + 3),
                           (long_prompt, 1 + 1)), [W, 3, 1]),
                (with_eos, ((prompt, 4 * W),), [W] * full + [last])):
            _traced()
            with _Served(artifact, 1, None) as s:
                pred = s.entry.predictor
                assert pred._fns, "the load did not warm the lane"
                assert pred.prompt_bucket(len(long_prompt)) \
                    > pred.prompt_bucket(len(prompt))
                on[0] = True
                for p, max_new in requests:
                    s.stream(p, max_new)
                on[0] = False
                assert _trips() == want
                assert events == []
    finally:
        on[0] = False


# ---------------------------------------------------------------------------
# The order of a dispatch's delivery and the next launch (PR 38): a full lane
# whose last dispatch ended nobody launches the next one FIRST, whatever
# waits behind it, and hands the tokens to the streams
# while the device runs; every other pass delivers, finishes, admits, prefills
# and launches, as ever.
# ---------------------------------------------------------------------------

from paddle_tpu.serving import batcher as batcher_mod            # noqa: E402
from paddle_tpu.serving.batcher import DecodeBatcher             # noqa: E402


class _Recorded(object):
    """A `DecodeBatcher` whose lane's session and whose streams write what
    they are asked into one list, in the order asked: ("launch", k) and
    ("fetch", k) of the k-th step dispatch, ("put", stream, n tokens),
    ("finish", stream, reason), ("fail", stream, error type).
    `at_fetch[k]()` runs inside the k-th fetch, before the lane sees its
    result."""

    def __init__(self, monkeypatch, artifact, slots, **kw):
        self.log = log = []
        self.pred = GenerativePredictor(artifact)
        self.batcher = DecodeBatcher(self.pred, n_slots=slots, **kw)
        self.lane = self.batcher._lanes[0]
        self.at_fetch = at_fetch = {}
        sess, n = self.lane.session, [0, 0]
        launch = getattr(sess, "launch_fused", None)
        fetch = getattr(sess, "fetch_fused", None)

        def launch_fused(*a, **k):
            log.append(("launch", n[0]))
            n[0] += 1
            return launch(*a, **k)

        def fetch_fused():
            at_fetch.get(n[1], lambda: None)()
            out = fetch()
            log.append(("fetch", n[1]))
            n[1] += 1
            return out
        if not self.lane.spec:
            sess.launch_fused, sess.fetch_fused = launch_fused, fetch_fused
        stream = batcher_mod.DecodeStream
        put, finish, fail = stream._put_tokens, stream._finish, stream._fail

        def _put_tokens(self_, toks, *stamps):
            log.append(("put", self_, len(toks)))
            return put(self_, toks, *stamps)

        def _finish(self_, reason, **k):
            log.append(("finish", self_, reason))
            return finish(self_, reason, **k)

        def _fail(self_, exc):
            log.append(("fail", self_, type(exc).__name__))
            return fail(self_, exc)
        monkeypatch.setattr(stream, "_put_tokens", _put_tokens)
        monkeypatch.setattr(stream, "_finish", _finish)
        monkeypatch.setattr(stream, "_fail", _fail)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.batcher.close(drain=False, timeout=10.0)

    def submit_together(self, requests, then=None, waiting=0, **kw):
        """Every request queued before the lane looks: it admits as many
        as it has slots in ONE pass.  `then(streams)` runs before it
        looks, too.  Behind them wait `waiting` requests of ONE token
        each: they end in their prefill and never ride a dispatch."""
        with self.batcher._cv:
            streams = [self.batcher.submit(p, max_new_tokens=m, **kw)
                       for p, m in requests]
            for _ in range(waiting):
                self.batcher.submit(PROMPTS[3], max_new_tokens=1)
            if then is not None:
                then(streams)
        return streams

    def between(self, k):
        """(what the streams were handed between fetch k-1 and launch k,
        between launch k and fetch k), first elements dropped: the kinds
        and streams."""
        at = {e: i for i, e in enumerate(self.log)
              if e[0] in ("launch", "fetch")}
        lo = at[("fetch", k - 1)] if k else -1
        mid, hi = at[("launch", k)], at[("fetch", k)]
        mine = [(i, e) for i, e in enumerate(self.log)
                if e[0] in ("put", "finish", "fail")]
        return ([e for i, e in mine if lo < i < mid],
                [e for i, e in mine if mid < i < hi])


def _early():
    return _step_attr("early")


def _wait_all(streams, timeout=120):
    for s in streams:
        s._done.wait(timeout)
        assert s.done()


@pytest.mark.parametrize("waiting", [0, 1, 2])
def test_a_full_lane_launches_before_it_delivers(models, monkeypatch,
                                                 waiting):
    """Two slots, two streams of three windows, nobody ends before the
    last, with nobody, one request or a lane's worth waiting behind them:
    dispatch 1 and 2 are launched BEFORE any token of the dispatch before
    them reaches a stream, and those tokens arrive before the fetch; the
    counter and the span attribute count the same dispatches."""
    endless = models[0]
    _traced()
    with _Recorded(monkeypatch, endless, 2) as r:
        a, b = r.submit_together([(PROMPTS[0], 1 + 3 * W),
                                  (PROMPTS[1], 1 + 3 * W)], waiting=waiting)
        _wait_all([a, b])
        assert _trips() == [W, W, W]
        assert _early() == [False, True, True]
        stats, = r.batcher.replica_stats()
        assert stats["early_launches"] == 2 and stats["batches"] == 3
        # the first dispatch follows the admissions: their prefills' first
        # tokens, then the launch
        before, during = r.between(0)
        assert [e[0] for e in before] == ["put", "put"] and not during
        for k in (1, 2):
            before, during = r.between(k)
            assert before == []
            assert sorted(e[:1] + e[2:] for e in during) == \
                [("put", W), ("put", W)]
            assert {e[1] for e in during} == {a, b}
        # the last dispatch ended both: delivered at once
        tail = [e for e in r.log[r.log.index(("fetch", 2)) + 1:]
                if e[1] in (a, b)]
        assert sorted(e[0] for e in tail) == ["finish", "finish",
                                              "put", "put"]
        assert r.lane.held is None
    for s, p in ((a, PROMPTS[0]), (b, PROMPTS[1])):
        assert s.tokens == greedy_decode(r.pred, p, 1 + 3 * W)[0]


def _order_finisher(r):
    """A ends with dispatch 0 and C takes its slot: dispatch 1 runs a full
    lane, yet it follows a finisher, an admission and a prefill."""
    a, b, c = r.submit_together([(PROMPTS[0], 1 + W), (PROMPTS[1], 1 + 4 * W),
                                 (PROMPTS[2], 1 + 2 * W)])
    _wait_all([a, b, c])
    assert _early() == [False, False, True, False]
    assert _trips() == [W] * 4 and _step_attr("slots") == [2, 2, 2, 1]
    before, during = r.between(1)
    assert not during and ("finish", a, "length") in before
    assert r.between(2)[0] == []
    return 1


def _order_cancelled(r):
    a, b = r.submit_together(
        [(PROMPTS[0], 1 + 4 * W), (PROMPTS[1], 1 + 2 * W + 2)],
        then=lambda streams: r.at_fetch.update({1: streams[0].cancel}))
    _wait_all([a, b])
    assert a.finish_reason == "cancelled"
    assert ("finish", a, "cancelled") in r.between(2)[0]
    return 1


def _order_expired(r):
    def expire():
        req, = [q for q in r.lane.assigned.values() if q.stream is a]
        req.deadline = time.monotonic() - 1e-3
    r.at_fetch[1] = expire
    a, b = r.submit_together([(PROMPTS[0], 1 + 4 * W),
                              (PROMPTS[1], 1 + 2 * W + 2)])
    _wait_all([a, b])
    # what the stream had been given, then the typed failure
    mine = [e for e in r.between(2)[0] if e[1] is a]
    assert [e[0] for e in mine] == ["put", "fail"]
    assert mine[-1][2] == "DeadlineExceeded"
    return 1


def _order_free_slot(r):
    """One stream on two slots: windows (a newcomer would wait for one to
    end), each delivered before the next is launched."""
    a, = r.submit_together([(PROMPTS[0], 1 + 2 * W + 2)])
    _wait_all([a])
    assert _trips() == [W, W, 2] and _step_attr("slots") == [1, 1, 1]
    return 0


@pytest.mark.parametrize("case", ["finisher", "cancelled", "expired",
                                  "free_slot"])
def test_any_other_pass_delivers_before_it_launches(models, monkeypatch,
                                                    case):
    """A dispatch that follows a finisher, a cancellation or an expiry, or
    runs with a slot free, is launched AFTER the delivery of the one before
    it, and a
    cancelled or expired stream leaves at the first dispatch boundary after
    the event (the next dispatch runs without it)."""
    endless = models[0]
    _traced()
    with _Recorded(monkeypatch, endless, 2) as r:
        want = globals()["_order_" + case](r)
        early = _early()
        stats, = r.batcher.replica_stats()
        assert stats["early_launches"] == sum(early) == want
        for k, flag in enumerate(early):
            before, during = r.between(k)
            assert not (during if not flag else before), (k, flag)
        if case in ("cancelled", "expired"):
            # dispatch 1 was launched early, saw the event and delivered at
            # once; dispatch 2 runs the one stream that is left
            assert early[:3] == [False, True, False]
            assert _step_attr("slots")[:3] == [2, 2, 1]


def test_a_speculative_lane_keeps_the_old_order(models, monkeypatch):
    endless = models[0]
    if "olmoe" in endless:
        pytest.skip("a routed stack has no speculative session")
    _traced()
    with _Recorded(monkeypatch, endless, 1,
                   draft=GenerativePredictor(endless), spec_k=2) as r:
        assert r.lane.spec
        a, = r.submit_together([(PROMPTS[0], 1 + 2 * W)])
        _wait_all([a])
        early = _early()
        assert early and not any(early)
        assert r.batcher.replica_stats()[0]["early_launches"] == 0
        assert r.lane.held is None
    assert a.tokens == greedy_decode(r.pred, PROMPTS[0], 1 + 2 * W)[0]


def _stack_artifact(name, root):
    if name in BLOCKS:
        return build_tiny_decode_model(str(root / name), eos_id=-1,
                                       **BLOCKS[name])
    if name == "lfm2":
        from tests.test_decode_hybrid import LFM2_BLOCK, TINY
        return build_tiny_decode_model(
            str(root / name), block=LFM2_BLOCK, **dict(TINY, eos_id=-1))
    if name == "ssm":
        from tests.test_decode_ssm import SSM_BLOCK, TINY
        return build_tiny_decode_model(
            str(root / name), block=SSM_BLOCK, **dict(TINY, eos_id=-1))
    from paddle_tpu.inference.decode import save_decode_model
    from tests.test_mla_decode import META, _drawn
    meta = dict(META, eos_id=-1)
    return save_decode_model(str(root / name), _drawn(meta), meta)


@pytest.mark.parametrize("stack", ["gpt2", "olmoe", "lfm2", "latent", "ssm"])
def test_streams_of_the_new_order_equal_the_plain_stream(stack, tmp_path):
    """Token for token: a full lane under windows (launch, deliver, fetch),
    the same lane pinned to one trip a dispatch, and `greedy_decode`, for
    each kind of stack the suites build."""
    artifact = _stack_artifact(stack, tmp_path)
    pred = GenerativePredictor(artifact)
    requests = [(PROMPTS[0], 1 + 2 * W + 3), (PROMPTS[1], 1 + 3 * W)]
    want = [greedy_decode(pred, p, m)[0] for p, m in requests]
    for cap in (None, 1):
        b = DecodeBatcher(GenerativePredictor(artifact), n_slots=2,
                          fuse_steps=cap)
        try:
            with b._cv:
                streams = [b.submit(p, max_new_tokens=m)
                           for p, m in requests]
            got = [s.result(timeout=120)[0].tolist() for s in streams]
            assert b.replica_stats()[0]["early_launches"] >= 2
        finally:
            b.close(drain=False, timeout=10.0)
        assert got == want, (stack, cap)


def test_a_disconnect_frees_its_slot_at_a_dispatch_boundary(models):
    """Through the server: two clients on a full lane that launches ahead
    of its deliveries (two more wait behind them), one goes away
    mid-stream.  Its slot goes to a waiting request long before the other
    stream ends, that stream is untouched, and the waiting requests are
    served from clean rows."""
    endless = models[0]
    set_dispatch_delay(0.005)
    _traced()
    done_at = {}

    def client(s, key, prompt, n):
        done_at[key] = (s.stream(prompt, n), time.monotonic())
    with _Served(endless, 2, None) as s:
        whole = s.stream(PROMPTS[1], 5 * W)[0]
        threads = [threading.Thread(target=client,
                                    args=(s, "stays", PROMPTS[0], 12 * W))]
        threads[0].start()
        cli = ServingClient(s.server.endpoint)
        it = cli.infer_stream("lm", PROMPTS[1], max_new_tokens=12 * W)
        seen = list(next(it))                   # admitted: the lane is full
        for key, prompt in (("w1", PROMPTS[2]), ("w2", PROMPTS[3])):
            threads.append(threading.Thread(target=client,
                                            args=(s, key, prompt, 5)))
            threads[-1].start()
        deadline = time.monotonic() + 30
        while len(s.batcher._pending) < 2 and time.monotonic() < deadline:
            time.sleep(0.002)
        mark = len(seen)
        for chunk in it:
            seen += chunk
            if len(seen) > mark + 2 * W:
                break
        it.close()
        cli.close()
        for t in threads:
            t.join(timeout=120)
        assert any(_early())
        pred = GenerativePredictor(endless)
    assert seen == whole[:len(seen)] or seen[:5 * W] == whole
    assert min(done_at["w1"][1], done_at["w2"][1]) < done_at["stays"][1]
    for key, prompt, n in (("stays", PROMPTS[0], 12 * W),
                           ("w1", PROMPTS[2], 5), ("w2", PROMPTS[3], 5)):
        assert done_at[key][0][0] == greedy_decode(pred, prompt, n)[0]


def test_a_held_delivery_and_a_finish_keep_a_streams_frames_in_order(
        models, monkeypatch):
    """One slot, chunks of more tokens than a window: the second dispatch
    fills a chunk, which is held back behind the third's launch, and the
    third's `_finish` flushes what is left: the chunk, the rest, then
    `done`, the plain stream when joined."""
    endless = models[0]
    n = 1 + 2 * W + 3
    with _Recorded(monkeypatch, endless, 1) as r:
        a, = r.submit_together([(PROMPTS[0], n)], chunk_tokens=W + 3)
        events = list(a.events(timeout=120))
        assert r.batcher.replica_stats()[0]["early_launches"] == 2
    assert [k for k, _ in events[:-1]] == ["tokens"] * (len(events) - 1)
    assert events[-1] == ("done", "length")
    assert [len(c) for _, c in events[:-1]] == [1 + 2 * W, 3]
    assert [t for _, c in events[:-1] for t in c] == \
        greedy_decode(r.pred, PROMPTS[0], n)[0]


@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning")
@pytest.mark.parametrize("lost", [True, False])
def test_a_failed_launch_delivers_what_was_held_before_it_fails(
        models, monkeypatch, lost):
    """The launch of dispatch 2 fails while dispatch 1's tokens are held.
    The lane's mesh died: the streams get those tokens, then the typed
    failure, in that order.  Any other exception kills the lane's thread,
    as it always did, and the tokens decided before it still reach their
    stream."""
    from paddle_tpu.parallel.mesh import MeshMemberLost
    endless = models[0]
    with _Recorded(monkeypatch, endless, 1) as r:
        sess, launch = r.lane.session, r.lane.session.launch_fused

        def failing(*a, **k):
            if ("fetch", 1) in r.log:
                raise MeshMemberLost("member gone") if lost \
                    else RuntimeError("a chaos hook")
            return launch(*a, **k)
        sess.launch_fused = failing
        a, = r.submit_together([(PROMPTS[0], 1 + 4 * W)])
        r.batcher._threads[0].join(timeout=120)
        assert not r.batcher._threads[0].is_alive()
        assert r.lane.held is None and bool(r.lane.dead) == lost
        events = []
        while not a._q.empty():
            events.append(a._q.get())
    assert [k for k, _ in events] == ["tokens"] * 3 + ["error"] * lost
    if lost:
        assert isinstance(events[-1][1], MeshMemberLost)
    assert [t for k, c in events if k == "tokens" for t in c] == \
        greedy_decode(r.pred, PROMPTS[0], 1 + 2 * W)[0]
