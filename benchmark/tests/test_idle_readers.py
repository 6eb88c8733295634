"""Tests of benchmark/idle.py and the per-layer readers that split a cell's
device idle time by the program's phase spans, on the CPU: spans and traces
written by hand, where the idle time under each phase is known, and the
small trace recorded on a v5e.

    python -m pytest benchmark/tests/test_idle_readers.py -q -m 'not slow'
"""

import json
import os
import types

import pytest

from benchmark import idle, xplane
from benchmark import run as bench_run

DATA = os.path.join(os.path.dirname(__file__), "data")
MONO = 50.0           # time.monotonic() at the trace's second 0, below


def reader(name):
    return bench_run.load_reader(name)


def trace_of(ops):
    """A one-device Trace whose second 0 is monotonic second MONO."""
    t = xplane.Trace({0: ops}, modules=[("jit_bench_anchor(1)", -1.0, 0.0)])
    t.set_anchor([(1000.0, MONO)])
    return t


@pytest.fixture(autouse=True)
def fresh_ring():
    from paddle_tpu.obs import tracing
    tracing.set_enabled(True)
    tracing.clear()
    yield
    tracing.clear()


# ---------------------------------------------------------------------------
# the shared arithmetic
# ---------------------------------------------------------------------------

def test_timeline_names_every_stretch_by_its_innermost_span():
    line = idle.innermost_timeline([
        ("run", 0.0, 10.0), ("feed", 0.0, 2.0), ("fetch", 6.0, 10.0),
        ("inner", 7.0, 8.0), ("other", 12.0, 13.0), ("empty", 3.0, 3.0)])
    assert line == [(0.0, 2.0, "feed"), (2.0, 6.0, "run"),
                    (6.0, 7.0, "fetch"), (7.0, 8.0, "inner"),
                    (8.0, 10.0, "fetch"), (12.0, 13.0, "other")]
    # spans of two threads that only overlap: the later start wins
    assert idle.innermost_timeline([("a", 0.0, 5.0), ("b", 3.0, 8.0)]) == [
        (0.0, 3.0, "a"), (3.0, 8.0, "b")]
    assert idle.innermost_timeline([]) == []


def test_a_gap_under_two_nested_spans_goes_to_the_inner_one():
    spans = [("serving/lane_iter", 0.0, 10.0), ("decode/launch", 2.0, 5.0),
             ("decode/fetch", 5.0, 9.0)]
    by_name, bare = idle.split([(1.0, 3.0), (4.0, 6.0), (9.5, 12.0)], spans)
    assert by_name == {"serving/lane_iter": 1.0 + 0.5,
                       "decode/launch": 1.0 + 1.0, "decode/fetch": 1.0}
    assert bare == pytest.approx(2.0)
    assert idle.split([], spans) == ({}, 0.0)
    assert idle.split([(0.0, 1.0)], []) == ({}, 1.0)


# ---------------------------------------------------------------------------
# training: a step of Executor.run
# ---------------------------------------------------------------------------

def stamp_executor_step(at, feed=0.8, dispatch=0.4, fetch=3.3, h2d=1000,
                        step=0):
    """One `Executor.run` of feed + dispatch + fetch seconds beginning at
    trace second `at`, into the program's ring as the executor stamps it."""
    from paddle_tpu.obs import tracing
    a = MONO + at
    b, c, d = a + feed, a + feed + dispatch, a + feed + dispatch + fetch
    tracing.stamp("executor/feed", a, b, kind="train", parent="executor/run",
                  step=step, h2d_bytes=h2d, cast_bytes=8)
    tracing.stamp("executor/dispatch", b, c, kind="train",
                  parent="executor/run", step=step, compiled=0,
                  state_host_bytes=0)
    tracing.stamp("executor/fetch", c, d, kind="train",
                  parent="executor/run", step=step, d2h_bytes=4)
    tracing.stamp("executor/run", a, d, kind="train", step=step, steps=1,
                  path="jit")


def test_executor_readers_sum_to_window_minus_busy_per_step(capsys):
    # two steps of 5 s: run 0..4.5 (feed 0.8, dispatch 0.4, fetch 3.3), the
    # device busy 1.0..4.0, the caller's own 0.5 s before the next call
    for k in range(2):
        stamp_executor_step(5.0 * k, step=k, h2d=1000 + k)
    trace = trace_of([("fusion.1", 1.0, 2.5), ("fusion.2", 2.5, 4.0),
                      ("fusion.1", 6.0, 9.0)])
    run = {"calls_window": (0.0, 10.0), "steps_in_trace": 2}
    got = {n: reader("executor_idle_ms_per_step." + n)(None, trace, run)
           for n in ("feed", "dispatch", "fetch")}
    assert got["feed"] == pytest.approx(800.0)
    assert got["dispatch"] == pytest.approx(200.0)       # 0.8..1.0
    assert got["fetch"] == pytest.approx(500.0 + 500.0)  # 4.0..4.5 + between
    busy = trace.busy_mean(0.0, 10.0)
    assert sum(got.values()) == pytest.approx((10.0 - busy) / 2 * 1e3)
    assert reader("feed_h2d_bytes_per_step")(None, trace, run) == 1000.5
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    (split,) = [l for l in lines if l["phase"] == "idle_split"]   # once
    assert split["share_under_no_span"] == pytest.approx(1.0 / 4.0)
    (counters,) = [l for l in lines if l["phase"] == "executor_counters"]
    assert counters["feed_cast_bytes"] == 8
    assert counters["state_host_bytes"] == 0


def test_executor_readers_keep_to_the_calls_window():
    for k in range(3):
        stamp_executor_step(5.0 * k, step=k)
    trace = trace_of([("fusion.1", 6.0, 9.0)])
    # only the middle call lies wholly inside
    run = {"calls_window": (5.0, 10.0), "steps_in_trace": 1}
    assert [n for n, _, _ in idle.executor_spans(trace, run)] == [
        "executor/feed", "executor/dispatch", "executor/fetch",
        "executor/run"]
    assert reader("executor_idle_ms_per_step.feed")(None, trace, run) == \
        pytest.approx(800.0)


def test_a_program_without_the_spans_reads_as_nothing():
    """The parent of the PR that added the spans: no `executor/*` in the
    ring, no `decode/*` or `serving/lane_iter` among the driver's spans,
    span dicts without `t0`.  Every reader returns None and none raises."""
    from paddle_tpu.obs import tracing
    tracing.add_span(tracing.Span("bench/else", kind="train"))
    trace = trace_of([("fusion.1", 1.0, 4.0)])
    run = {"calls_window": (0.0, 5.0), "steps_in_trace": 1}
    for n in ("executor_idle_ms_per_step.feed",
              "executor_idle_ms_per_step.dispatch",
              "executor_idle_ms_per_step.fetch", "feed_h2d_bytes_per_step"):
        assert reader(n)(None, trace, run) is None
        assert reader(n)(None, trace, {}) is None
    old = [{"name": "serving/decode_step", "t0": MONO + 1.0,
            "t1": MONO + 4.0, "attrs": {"tokens": 2}}]
    run = {"window": (MONO, MONO + 5.0), "trace_window": (0.0, 5.0),
           "records": []}
    for n in ("decode_h2d_bytes_per_round", "decode_launch_ms_per_round",
              "decode_fetch_ms_per_round", "decode_idle_ms_per_round.launch",
              "decode_idle_ms_per_round.fetch",
              "decode_idle_ms_per_round.lane", "lane_self_ms_per_round",
              "prefill_share_of_lane", "token_wire_ms_p50"):
        assert reader(n)(old, trace, run) is None, n
        assert reader(n)([], trace, run) is None, n
    assert reader("decode_round_ms.deep")(old, trace, run) == \
        pytest.approx(3000.0)


def test_recorded_trace_idle_is_shared_out_whole():
    """The trace recorded on a v5e (four small calls with sleeps between):
    each host call stands for one `Executor.run` whose three children tile
    it; the three readers then sum to the idle time of the calls' window
    per call, and the sleeps between calls go to `.fetch`."""
    from paddle_tpu.obs import tracing
    t = xplane.read_trace(os.path.join(DATA, "tiny_tpu.xplane.pb"))
    host = bench_run.load_json(os.path.join(DATA, "tiny_tpu.json"))
    t.set_anchor([tuple(a) for a in host["anchors"]])
    for k, (a, b) in enumerate(host["calls"]):
        cut1, cut2 = a + 0.2 * (b - a), a + 0.5 * (b - a)
        for name, s, e in (("feed", a, cut1), ("dispatch", cut1, cut2),
                           ("fetch", cut2, b)):
            tracing.stamp("executor/" + name, s, e, kind="train",
                          parent="executor/run", step=k)
        tracing.stamp("executor/run", a, b, kind="train", step=k)
    c0 = t.from_monotonic(host["calls"][0][0])
    c1 = t.from_monotonic(host["calls"][-1][1])
    run = {"calls_window": (c0, c1), "steps_in_trace": 4}
    got = {n: reader("executor_idle_ms_per_step." + n)(None, t, run)
           for n in ("feed", "dispatch", "fetch")}
    assert all(v is not None and v >= 0.0 for v in got.values())
    idle_ms = (c1 - c0 - t.busy_mean(c0, c1)) / 4 * 1e3
    assert sum(got.values()) == pytest.approx(idle_ms, rel=1e-6)
    in_calls = sum(b - a for a, b in host["calls"])
    # at least the three sleeps between the calls are the caller's own time
    assert got["fetch"] * 4 >= (c1 - c0 - in_calls) * 1e3


# ---------------------------------------------------------------------------
# serving: a decode round
# ---------------------------------------------------------------------------

def span(name, a, b, **attrs):
    return {"name": name, "t0": MONO + a, "t1": MONO + b, "attrs": attrs}


def lane_rounds():
    """Two lane iterations of 10 s as the serving driver hands them over.
    Round 0 admits one request (prefill 0..2: put 0..0.1, launch 0.1..1.2,
    fetch 1.2..1.9), then dispatches: decode_step 2..9 (put 2..2.2, launch
    2.2..4, fetch 4..8.5), emit 9..9.6, end 10.  Round 1 the same without
    the prefill, its step at 12..19."""
    out = [span("serving/prefill_compute", 0.0, 2.0, prompt=5),
           span("decode/put", 0.0, 0.1, phase="prefill", bytes=0),
           span("decode/launch", 0.1, 1.2, phase="prefill", h2d_bytes=900),
           span("decode/fetch", 1.2, 1.9, phase="prefill", d2h_bytes=4)]
    for k, at in enumerate((0.0, 10.0)):
        out += [
            span("decode/put", at + 2.0, at + 2.2, phase="step", round=k,
                 bytes=0),
            span("decode/launch", at + 2.2, at + 4.0, phase="step", round=k,
                 h2d_bytes=700 + k),
            span("decode/fetch", at + 4.0, at + 8.5, phase="step", round=k,
                 d2h_bytes=8),
            span("serving/emit", at + 9.0, at + 9.6, round=k, tokens=2),
            span("serving/decode_step", at + 2.0, at + 9.0, round=k,
                 tokens=2),
            span("serving/lane_iter", at, at + 10.0, round=k,
                 admits=1 - k, emitted=2)]
    return out


def test_decode_readers_on_a_known_round(capsys):
    spans = lane_rounds()
    # device: the prefill 0.5..1.5, each step's program 3..8 of its round
    trace = trace_of([("fusion.9", 0.5, 1.5), ("fusion.1", 3.0, 8.0),
                      ("fusion.1", 13.0, 18.0)])
    rec = types.SimpleNamespace(
        token_times=[MONO + 2.0 + 0.3, MONO + 9.0 + 0.7, MONO + 19.0 + 0.5])
    run = {"window": (MONO, MONO + 20.0), "trace_window": (0.0, 20.0),
           "records": [rec]}
    read = {n: reader(n)(spans, trace, run) for n in (
        "decode_h2d_bytes_per_round", "decode_launch_ms_per_round",
        "decode_fetch_ms_per_round", "decode_idle_ms_per_round.launch",
        "decode_idle_ms_per_round.fetch", "decode_idle_ms_per_round.lane",
        "lane_self_ms_per_round", "prefill_share_of_lane",
        "token_wire_ms_p50")}
    assert read["decode_h2d_bytes_per_round"] == 700.5
    assert read["decode_launch_ms_per_round"] == pytest.approx(2000.0)
    assert read["decode_fetch_ms_per_round"] == pytest.approx(4500.0)
    # idle under put + launch: prefill 0..0.5 (0.1 put + 0.4 launch), then
    # 2..3 and 12..13 of the rounds -> 2.5 s over 2 rounds
    assert read["decode_idle_ms_per_round.launch"] == pytest.approx(1250.0)
    # under fetch: prefill 1.5..1.9, rounds 8..8.5 and 18..18.5
    assert read["decode_idle_ms_per_round.fetch"] == pytest.approx(700.0)
    # the lane's own: 1.9..2 + 8.5..10 + 10..12 + 18.5..20
    assert read["decode_idle_ms_per_round.lane"] == pytest.approx(
        (0.1 + 1.5 + 2.0 + 1.5) / 2 * 1e3)
    busy = trace.busy_mean(0.0, 20.0)
    assert (read["decode_idle_ms_per_round.launch"]
            + read["decode_idle_ms_per_round.fetch"]
            + read["decode_idle_ms_per_round.lane"]) == pytest.approx(
        (20.0 - busy) / 2 * 1e3)
    assert read["lane_self_ms_per_round"] == pytest.approx(
        (20.0 - 14.0 - 2.0) / 2 * 1e3)
    assert read["prefill_share_of_lane"] == pytest.approx(10.0)
    # 0.3 after the prefill, 0.7 and 0.5 after a step's end
    assert read["token_wire_ms_p50"] == pytest.approx(500.0)
    out = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    assert len([l for l in out if l["phase"] == "idle_split"]) == 1


def test_a_window_holds_parts_of_rounds():
    # dispatches every 10 s from second 2; the last one lasts to 39
    starts = [2.0, 12.0, 22.0, 32.0]
    assert idle.rounds_inside(starts, 39.0, 0.0, 40.0) == pytest.approx(4.0)
    assert idle.rounds_inside(starts, 39.0, 7.0, 27.0) == pytest.approx(
        0.5 + 1.0 + 0.5)
    assert idle.rounds_inside(starts, 39.0, 32.0, 35.5) == pytest.approx(0.5)
    assert idle.rounds_inside([], 0.0, 0.0, 10.0) == 0.0


def test_rounds_that_dispatch_twice_are_summed_by_round():
    spans = [span("decode/launch", 0.0, 1.0, phase="step", round=0),
             span("decode/launch", 2.0, 2.5, phase="step", round=0),
             span("decode/put", 5.0, 5.5, phase="step", round=1),
             span("decode/launch", 9.0, 9.5, phase="prefill")]
    assert sorted(idle.step_phase_ms(
        spans, (MONO, MONO + 10.0), ("decode/put", "decode/launch"))) == \
        pytest.approx([500.0, 1500.0])


# ---------------------------------------------------------------------------
# the two SHARES that count a dispatch's trips (a span is `trips` decode
# steps since PR 30; read a span a step, the one passed 100% sevenfold and
# the other read a seventh)
# ---------------------------------------------------------------------------

def dispatch(at, **attrs):
    return span("serving/decode_step", at, at + 0.5, **attrs)


@pytest.mark.parametrize("case,steps,want", [
    ("a_step_a_span", [dispatch(1.0, tokens=4), dispatch(2.0, tokens=3)],
     100.0 * 7 / (2 * 4)),
    # a full window of 8 trips, then one whose streams ended a trip early
    ("dispatches_of_trips", [dispatch(1.0, tokens=32, trips=8),
                             dispatch(2.0, tokens=26, trips=7)],
     100.0 * 58 / (15 * 4)),
    ("with_and_without_the_attribute", [dispatch(1.0, tokens=32, trips=8),
                                        dispatch(2.0, tokens=4)], 100.0),
    ("spans_outside_the_window_do_not_count",
     [dispatch(1.0, tokens=16, trips=8), dispatch(30.0, tokens=32, trips=8)],
     50.0),
    ("no_step", [span("serving/lane_iter", 1.0, 2.0)], None)])
def test_slots_busy_share_counts_a_dispatchs_trips(case, steps, want):
    got = reader("slots_busy_share")(
        steps, None, {"window": (MONO, MONO + 20.0), "slots": 4})
    assert got == (want if want is None else pytest.approx(want, rel=1e-12))
    assert got is None or got <= 100.0


@pytest.mark.parametrize("trips,calls", [
    # a span without the attribute is one step over the lengths at its start
    (None, [[13, 33]]),
    # four trips: every stream a token longer at each, the one with two
    # tokens of budget left gone after the second
    (4, [[13, 33], [14, 34], [15], [16]])])
def test_decode_attention_roofline_counts_every_trip_of_a_dispatch(trips,
                                                                   calls):
    from benchmark import costs, peaks
    heads, dh, layers = 4, 8, 3
    # the kernel: 2 s of device time inside the profiled 0..20
    trace = trace_of([("decode_attention_kernel.1", 3.0, 4.0),
                      ("fusion.2", 4.0, 5.0),
                      ("decode_attention_kernel.1", 13.0, 14.0)])

    def stream(prompt, max_new, got):
        return types.SimpleNamespace(
            prompt_len=prompt, max_new=max_new, done=None,
            token_times=[MONO + 0.5 + 0.1 * k for k in range(got)])
    records = [stream(10, 20, 3), stream(30, 5, 3),
               # ended before the dispatch; not begun at it
               types.SimpleNamespace(prompt_len=9, max_new=2, done=MONO + 1.0,
                                     token_times=[MONO + 0.6, MONO + 0.9]),
               types.SimpleNamespace(prompt_len=9, max_new=2, done=None,
                                     token_times=[MONO + 9.0])]
    attrs = {} if trips is None else {"trips": trips}
    spans = [span("serving/decode_step", 2.0, 8.0, tokens=8, **attrs),
             span("serving/decode_step", 19.0, 21.0, tokens=8, trips=8)]
    run = {"kernel_match": {"decode_attention": "decode_attention_kernel"},
           "trace_window": (0.0, 20.0),
           "trace_window_monotonic": (MONO, MONO + 20.0),
           "meta": {"n_heads": heads, "d_model": heads * dh,
                    "n_layers": layers},
           "records": records, "device_kind": "TPU v5 lite"}
    flops = bytes_ = 0.0
    for lengths in calls:
        f, b = costs.decode_attention_cost(lengths, heads, dh)
        flops, bytes_ = flops + f * layers, bytes_ + b * layers
    pk = peaks.peaks_for("TPU v5 lite")
    least, bound = costs.roofline_seconds(
        flops, bytes_, pk["flops_per_s"]["float32_default_precision"],
        pk["hbm_bytes_per_s"])
    assert bound == "memory"
    got = reader("decode_attention_roofline")(spans, trace, run)
    assert got == pytest.approx(100.0 * least / 2.0, rel=1e-12)
    # and nothing to read is nothing, never 0
    assert reader("decode_attention_roofline")(
        spans, trace_of([("fusion.2", 4.0, 5.0)]), run) is None
    assert reader("decode_attention_roofline")(
        spans, trace, dict(run, kernel_match={})) is None


# ---------------------------------------------------------------------------
# the new cell end to end at tiny size, off the chip
# ---------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.parametrize("trace", [0, 1])
def test_deep_cell_rehearsal(trace):
    from benchmark.tests.rehearse import rehearse
    manifest = bench_run.load_json(bench_run.MANIFEST)
    # 3 s: at tiny size a round is ~5 ms, and the yardstick's breakdown
    # costs gaps x host spans on the CPU stand-in trace's many events
    rc, last, lines = rehearse("gpt2s_decode_deep", trace, seconds=3.0)
    assert rc == 0, lines[-5:]
    assert last["correct"] is True and last["failed"] == 0
    want = manifest["per_layer"] if trace else manifest["end_to_end"]
    names = {m["name"] for m in want if "workloads" not in m
             or "gpt2s_decode_deep" in m["workloads"]}
    names.discard("decode_attention_roofline")      # no Mosaic call on CPU
    assert set(last["metrics"]) == names
