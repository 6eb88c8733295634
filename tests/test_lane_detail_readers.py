"""The nine readers PR 39 adds (benchmark/lane_detail.py and its files under
benchmark/layers/), on spans and device traces written by hand, where the
idle time under each span is known:

* the four `lane_idle_ms_per_round.*` share out exactly what
  `decode_idle_ms_per_round.lane` reads in one piece;
* `finish_ms_per_ender`, `slot_free_ms_per_ender` and the three
  `token_out_ms_per_frame.*` are means over the measured window;
* on the spans of a program without `serving/finish`, `serving/slot_free`
  and `serving/stream_out` (this PR's parent) the readers of those return
  None and none raises.

The helpers are those of benchmark/tests/test_idle_readers.py.
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import pytest                                        # noqa: E402

from benchmark import run as bench_run               # noqa: E402
from benchmark import xplane                         # noqa: E402

MONO = 50.0           # time.monotonic() at the trace's second 0, below
PARTS = ("prefill_host", "emit", "finish", "other")
NEW_SPAN_READERS = (
    "lane_idle_ms_per_round.emit", "lane_idle_ms_per_round.finish",
    "finish_ms_per_ender", "slot_free_ms_per_ender",
    "token_out_ms_per_frame.lane", "token_out_ms_per_frame.wake",
    "token_out_ms_per_frame.send")
NINE = tuple("lane_idle_ms_per_round." + p for p in PARTS) \
    + NEW_SPAN_READERS[2:]
DECODE_CELLS = ["gpt2s_decode_saturated", "gpt2s_decode_deep",
                "olmoe_decode_saturated", "lfm2_decode_saturated",
                "pangu_decode_saturated",
                # PR 42's cell joins every list that holds the five
                "falconh1_decode_saturated",
                # ... and PR 44's every list that holds the six
                "kexaone_decode_mixed_len",
                # ... and PR 48's every list that holds the seven: the
                # lane's spans are the same whatever the stack's layers
                "minicpmsala_longdoc_mixed",
                # ... and PR 51's every list that holds the eight
                "mimov2flash_reasoning_decode",
                # ... and PR 56's every list that holds the nine
                "granite4hs_decode_saturated"]


def reader(name):
    return bench_run.load_reader(name)


def trace_of(ops):
    """A one-device Trace whose second 0 is monotonic second MONO."""
    t = xplane.Trace({0: ops}, modules=[("jit_bench_anchor(1)", -1.0, 0.0)])
    t.set_anchor([(1000.0, MONO)])
    return t


def span(name, a, b, **attrs):
    return {"name": name, "t0": MONO + a, "t1": MONO + b, "attrs": attrs}


def lane_rounds(children=True):
    """Three lane iterations of 10 s.  Round 0 admits one request (prefill
    0..2: put 0..0.1, launch 0.1..1.2, fetch 1.2..1.9), dispatches
    (decode_step 2..9: put 2..2.2, launch 2.2..4, fetch 4..8.5) and delivers
    at once: emit 9..9.6, in it one finish 9.2..9.5 whose slot_free is
    9.3..9.4.  Round 1 (step 12..19) delivers puts alone, 19..19.6.  Round
    2 launched early: the delivery of ITS predecessor is held and lies
    between its launch and its fetch, 24..24.5 (step 22..29: put 22..22.2,
    launch 22.2..24, fetch 24.5..28.5), and its own delivery, 29..29.5,
    ends two requests (finishes 29.0..29.2 and 29.2..29.5).  `children`
    False: the spans of the program before PR 39."""
    out = [span("serving/prefill_compute", 0.0, 2.0, prompt=5),
           span("decode/put", 0.0, 0.1, phase="prefill"),
           span("decode/launch", 0.1, 1.2, phase="prefill", h2d_bytes=900),
           span("decode/fetch", 1.2, 1.9, phase="prefill", d2h_bytes=4)]
    for k, at in enumerate((0.0, 10.0, 20.0)):
        fetch_at = at + (4.5 if k == 2 else 4.0)
        out += [
            span("decode/put", at + 2.0, at + 2.2, phase="step", round=k),
            span("decode/launch", at + 2.2, at + 4.0, phase="step", round=k,
                 h2d_bytes=700),
            span("decode/fetch", fetch_at, at + 8.5, phase="step", round=k,
                 d2h_bytes=8),
            span("serving/decode_step", at + 2.0, at + 9.0, round=k,
                 tokens=2, early=k == 2),
            span("serving/lane_iter", at, at + 10.0, round=k,
                 admits=int(k == 0), emitted=2)]
    out += [span("serving/emit", 9.0, 9.6, round=0, tokens=2, puts=1,
                 enders=1),
            span("serving/emit", 19.0, 19.6, round=1, tokens=2, puts=2,
                 enders=0),
            span("serving/emit", 24.0, 24.5, round=1, tokens=2, puts=2,
                 enders=0),
            span("serving/emit", 29.0, 29.5, round=2, tokens=2, puts=0,
                 enders=2)]
    if children:
        out += [span("serving/finish", 9.2, 9.5, round=0, order=0, enders=1),
                span("serving/slot_free", 9.3, 9.4, round=0, slot=1),
                span("serving/finish", 29.0, 29.2, round=2, order=0,
                     enders=2),
                span("serving/slot_free", 29.05, 29.1, round=2, slot=0),
                span("serving/finish", 29.2, 29.5, round=2, order=1,
                     enders=2),
                span("serving/slot_free", 29.25, 29.4, round=2, slot=1)]
    return out


def stream_out(at, frames, lane, wake, send, **more):
    return span("serving/stream_out", at, at + 1.0, frames=frames,
                tokens=8 * frames, bytes=100 * frames, lane_ms_sum=lane,
                wake_ms_sum=wake, wake_ms_max=wake, send_ms_sum=send,
                send_ms_max=send, **more)


# the device: the prefill 0.5..1.5, each step's program 3..8 of its round
# (the third's from 4.2: the chip waits for the held delivery's first 0.2 s)
DEVICE = [("fusion.9", 0.5, 1.5), ("fusion.1", 3.0, 8.0),
          ("fusion.1", 13.0, 18.0), ("fusion.1", 24.2, 28.0)]


def run_facts():
    return {"window": (MONO, MONO + 30.0), "trace_window": (0.0, 30.0),
            "records": []}


@pytest.fixture(autouse=True)
def fresh_ring():
    from paddle_tpu.obs import tracing
    tracing.set_enabled(True)
    tracing.clear()
    yield
    tracing.clear()


# ---------------------------------------------------------------------------
# the four parts of `decode_idle_ms_per_round.lane`
# ---------------------------------------------------------------------------

# idle under `serving/lane_iter` outside `decode/*`, three rounds:
#   1.9..2 (the prefill's host side), 8.5..13, 18.5..22, 24..24.2 (the held
#   delivery; 24.2..24.5 the device runs), 28.5..30
WANT_S = {
    "prefill_host": 0.1,
    # 9..9.2 + 9.5..9.6, 19..19.6, the held 24..24.2; 29..29.5 is finishes
    "emit": 0.3 + 0.6 + 0.2,
    "finish": 0.3 + 0.5,
    # 8.5..9 + 9.6..12, 18.5..19 + 19.6..22, 28.5..29 + 29.5..30
    "other": 0.5 + 2.4 + 0.5 + 2.4 + 0.5 + 0.5}


@pytest.mark.parametrize("part", PARTS)
def test_each_part_of_the_lanes_idle_time_on_known_rounds(part):
    got = reader("lane_idle_ms_per_round." + part)(
        lane_rounds(), trace_of(DEVICE), run_facts())
    assert got == pytest.approx(WANT_S[part] / 3 * 1e3)


def test_the_four_parts_sum_to_the_lane_reading(capsys):
    spans, trace, run = lane_rounds(), trace_of(DEVICE), run_facts()
    whole = reader("decode_idle_ms_per_round.lane")(spans, trace, run)
    parts = [reader("lane_idle_ms_per_round." + p)(spans, trace, run)
             for p in PARTS]
    assert whole == pytest.approx(sum(WANT_S.values()) / 3 * 1e3)
    assert sum(parts) == pytest.approx(whole, rel=1e-12)
    # and with the launch and fetch idle, to the whole of the chip's idle
    # time a round: nothing is counted twice, nothing is lost
    rest = sum(reader("decode_idle_ms_per_round." + n)(spans, trace, run)
               for n in ("launch", "fetch"))
    busy = trace.busy_mean(0.0, 30.0)
    assert sum(parts) + rest == pytest.approx((30.0 - busy) / 3 * 1e3)
    # one split a `what`, however many readers ask
    import json
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    assert sorted(l["what"] for l in lines if l["phase"] == "idle_split") \
        == ["decode_round", "lane_detail"]


def test_a_sub_window_keeps_the_identity():
    # the profiled 3 s of a traced run cut rounds at both ends
    spans, trace = lane_rounds(), trace_of(DEVICE)
    run = dict(run_facts(), trace_window=(7.0, 26.0))
    whole = reader("decode_idle_ms_per_round.lane")(spans, trace, run)
    parts = [reader("lane_idle_ms_per_round." + p)(spans, trace, run)
             for p in PARTS]
    assert whole > 0.0 and sum(parts) == pytest.approx(whole, rel=1e-12)


# ---------------------------------------------------------------------------
# the means over the measured window
# ---------------------------------------------------------------------------

def test_finish_and_slot_free_are_means_over_the_windows_enders():
    spans, run = lane_rounds(), run_facts()
    assert reader("finish_ms_per_ender")(spans, None, run) == \
        pytest.approx((300.0 + 200.0 + 300.0) / 3)
    assert reader("slot_free_ms_per_ender")(spans, None, run) == \
        pytest.approx((100.0 + 50.0 + 150.0) / 3)
    # spans that began outside the measured window do not count
    run = dict(run, window=(MONO + 20.0, MONO + 30.0))
    assert reader("finish_ms_per_ender")(spans, None, run) == \
        pytest.approx(250.0)
    assert reader("slot_free_ms_per_ender")(spans, None, run) == \
        pytest.approx(100.0)


@pytest.mark.parametrize("case,outs,want", [
    # 4 + 12 frames: the mean over FRAMES, not over requests
    ("two_requests", [stream_out(1.0, 4, 2.0, 8.0, 1.0),
                      stream_out(2.0, 12, 6.0, 40.0, 7.0)],
     (8.0 / 16, 48.0 / 16, 8.0 / 16)),
    # a request that sent no chunk (shed, cancelled in the queue) adds none
    ("one_without_frames", [stream_out(1.0, 4, 2.0, 8.0, 1.0),
                            stream_out(2.0, 0, 0.0, 0.0, 0.0)],
     (0.5, 2.0, 0.25)),
    ("outside_the_window", [stream_out(1.0, 4, 2.0, 8.0, 1.0),
                            stream_out(45.0, 4, 200.0, 800.0, 100.0)],
     (0.5, 2.0, 0.25)),
    ("no_frame_at_all", [stream_out(1.0, 0, 0.0, 0.0, 0.0)],
     (None, None, None)),
    ("no_request", [], (None, None, None))])
def test_token_out_is_a_mean_over_the_windows_frames(case, outs, want):
    spans = lane_rounds() + outs
    got = tuple(reader("token_out_ms_per_frame." + p)(spans, None,
                                                      run_facts())
                for p in ("lane", "wake", "send"))
    assert got == tuple(w if w is None else pytest.approx(w) for w in want)


def write_pass(at, frames, enders=0, streams=None):
    return span("serving/write_pass", at, at + 0.01, frames=frames,
                streams=frames if streams is None else streams,
                enders=enders, bytes=100 * (frames + enders), backlogged=0)


@pytest.mark.parametrize("case,passes,want", [
    # a delivery of 88 chunks, its enders' 8 flushes, nine prefills' firsts
    ("a_lane_iteration", [write_pass(1.0, 88), write_pass(1.1, 8, 8)]
     + [write_pass(2.0 + k, 1) for k in range(9)], (88 + 8 + 9) / 11.0),
    # a pass that carried terminal frames alone counts, with no frame
    ("enders_alone", [write_pass(1.0, 4), write_pass(1.5, 0, 2)], 2.0),
    ("outside_the_window", [write_pass(1.0, 6), write_pass(45.0, 90)], 6.0),
    # the writer's parent: a handler thread a stream, no such span
    ("no_pass", [], None)])
def test_token_out_frames_per_pass_is_a_mean_over_the_windows_passes(
        case, passes, want):
    got = reader("token_out_frames_per_pass")(lane_rounds() + passes, None,
                                              run_facts())
    assert got == (None if want is None else pytest.approx(want))


def test_token_out_frames_per_pass_is_declared_for_the_decode_cells():
    manifest = bench_run.load_json(bench_run.MANIFEST)
    (m,) = [m for m in manifest["per_layer"]
            if m["name"] == "token_out_frames_per_pass"]
    assert m == {"name": "token_out_frames_per_pass", "unit": "frames",
                 "better": "higher", "source": "program_span",
                 "layer": "serving front", "moves": "tokens_per_s",
                 "workloads": DECODE_CELLS}
    # (last but for the eight readers PR 48, the one PR 49, the one
    # PR 50, the three PR 51, the one PR 53, the three PR 54, the one
    # PR 55 and the two PR 56 appended behind it)
    assert manifest["per_layer"][-22] is m
    assert os.path.exists(os.path.join(bench_run.LAYERS_DIR,
                                       m["name"] + ".py"))


def step_fetch(at, **attrs):
    return span("decode/fetch", at, at + 0.5, phase="step", trips=8, **attrs)


@pytest.mark.parametrize("case,fetches,want", [
    # 24 slots x 2 heads x 2 sparse layers x 8 trips of 64 tiles, 8 a step
    ("long_contexts", [step_fetch(1.0, kv_blocks_live=49152,
                                  kv_grid_steps=6144)] * 3, 8.0),
    # a short stream's last step is part empty: tiles over steps, summed
    # over the window's dispatches and not a mean of their ratios
    ("a_short_stream", [step_fetch(1.0, kv_blocks_live=640, kv_grid_steps=80),
                        step_fetch(2.0, kv_blocks_live=36, kv_grid_steps=12),
                        step_fetch(3.0, kv_blocks_live=4, kv_grid_steps=4)],
     680 / 96.0),
    ("outside_the_window", [
        step_fetch(1.0, kv_blocks_live=128, kv_grid_steps=16),
        step_fetch(45.0, kv_blocks_live=5, kv_grid_steps=5)], 8.0),
    # a prefill's fetch carries no such counters, and is not a step's
    ("a_prefill", [span("decode/fetch", 1.0, 1.5, phase="prefill",
                        kv_blocks_live=9, kv_grid_steps=9),
                   step_fetch(2.0, kv_blocks_live=12, kv_grid_steps=3)], 4.0),
    # the kernel's parent: one tile a step and no counter of the steps;
    # a stack without sparse layers: neither counter
    ("the_parent", [step_fetch(1.0, kv_blocks_live=49152,
                               kv_blocks_total=393216, selected_blocks=6144)],
     None),
    ("no_sparse_layer", [step_fetch(1.0)], None),
    ("nothing_ran", [step_fetch(1.0, kv_blocks_live=0, kv_grid_steps=0)],
     None),
    ("no_fetch", [], None)])
def test_sparse_tiles_per_grid_step_is_tiles_over_steps(case, fetches, want):
    got = reader("sparse_tiles_per_grid_step")(lane_rounds() + fetches, None,
                                               run_facts())
    assert got == (None if want is None else pytest.approx(want))


def test_sparse_tiles_per_grid_step_is_declared_for_its_cell_alone():
    manifest = bench_run.load_json(bench_run.MANIFEST)
    (m,) = [m for m in manifest["per_layer"]
            if m["name"] == "sparse_tiles_per_grid_step"]
    assert m == {"name": "sparse_tiles_per_grid_step", "unit": "tiles",
                 "better": "higher", "source": "program_counter",
                 "layer": "kernels", "moves": "tokens_per_s",
                 "workloads": ["minicpmsala_longdoc_mixed"]}
    # (last but for the one reader PR 50, the three PR 51, the one
    # PR 53, the three PR 54, the one PR 55 and the two PR 56 appended
    # behind it)
    assert manifest["per_layer"][-13] is m
    assert os.path.exists(os.path.join(bench_run.LAYERS_DIR,
                                       m["name"] + ".py"))
    # the one cell whose stack has sparse layers
    sparse = [w["name"] for w in manifest["workloads"]
              if "sparse_attention" in bench_run.load_json(os.path.join(
                  REPO, [c for c in manifest["configs"]
                         if c["name"] == w["config"]][0]["file"])).get(
                  "model", {}).get("layer_types", [])]
    assert sparse == m["workloads"]


# ---------------------------------------------------------------------------
# PR 50: `sparse_prefill_kernel_ms_per_prefill`, the flash body of a
# prefill's stage 2 by the NAME of its Mosaic call
# ---------------------------------------------------------------------------

def _prefill(a, b, prompt=9000):
    return span("serving/prefill_compute", a, b, prompt=prompt)


def _kernel(n, a, b):
    return ("%%sparse_prefill_attention.%d = f32[2048,4096]{1,0} "
            "custom-call(%%fusion.321, %%copy.1), custom_call_target="
            "\"tpu_custom_call\"" % n, a, b)


# two prefills inside the sub-window 0..10 (two sparse layers' calls in
# three chunks of the first, 0.6 s; one chunk of the second, 0.3 s), a
# third that straddles the sub-window's end, and what must not count: the
# step's kernel, a consumer that NAMES the call among its operands, the
# plain-XLA operations of the scope
_KERNEL_DEVICE = [
    _kernel(4, 1.0, 1.1), _kernel(5, 1.2, 1.3), _kernel(4, 1.4, 1.5),
    _kernel(5, 1.6, 1.7), _kernel(4, 1.8, 1.9), _kernel(5, 2.0, 2.1),
    ("%fusion.701 = f32[2048,4096]{1,0} fusion(%sparse_prefill_attention.4)",
     2.1, 2.4),
    ("%sparse_decode_attention.3 = f32[24,2,16,128]{3,2,1,0} custom-call("
     "%p.1)", 3.0, 3.5),
    _kernel(4, 5.0, 5.1), _kernel(5, 5.2, 5.4),
    ("%copy_bitcast_fusion.2 = f32[24576,256]{1,0} fusion(%p.2)", 5.4, 5.6),
    _kernel(4, 9.5, 9.9), _kernel(5, 10.2, 10.6)]


@pytest.mark.parametrize("case,prefills,device,want", [
    ("two_prefills", [_prefill(0.5, 2.5), _prefill(4.5, 6.0)],
     _KERNEL_DEVICE, 1e3 * (0.6 + 0.3) / 2),
    # a prefill that ends past the sub-window is not counted, nor its calls
    ("one_straddles_the_end", [_prefill(0.5, 2.5), _prefill(4.5, 6.0),
                               _prefill(9.0, 11.0)],
     _KERNEL_DEVICE, 1e3 * (0.6 + 0.3) / 2),
    # a call outside every prefill's span (a warm-up's) is nobody's
    ("a_call_outside_the_spans", [_prefill(4.5, 6.0)], _KERNEL_DEVICE,
     1e3 * 0.3),
    # the parent: stage 2 in plain XLA, no such event -> no reading
    ("the_parent", [_prefill(0.5, 2.5)],
     [e for e in _KERNEL_DEVICE
      if not e[0].startswith("%sparse_prefill_attention")], None),
    ("no_prefill_in_the_window", [_prefill(12.0, 13.0)], _KERNEL_DEVICE,
     None),
    ("nothing", [], [], None)])
def test_sparse_prefill_kernel_ms_per_prefill_reads_the_call_by_its_name(
        case, prefills, device, want):
    run = dict(run_facts(), trace_window=(0.0, 10.0),
               trace_window_monotonic=(MONO, MONO + 10.0))
    got = reader("sparse_prefill_kernel_ms_per_prefill")(
        prefills, trace_of(device), run)
    assert got == (None if want is None else pytest.approx(want))


def test_sparse_prefill_kernel_ms_per_prefill_is_declared_last():
    manifest = bench_run.load_json(bench_run.MANIFEST)
    # (last but for the three readers PR 51, the one PR 53, the three
    # PR 54, the one PR 55 and the two PR 56 appended behind it)
    assert manifest["per_layer"][-12] == {
        "name": "sparse_prefill_kernel_ms_per_prefill", "unit": "ms",
        "better": "lower", "source": "device_trace", "layer": "kernels",
        "moves": "tokens_per_s", "workloads": ["minicpmsala_longdoc_mixed"]}
    assert len(manifest["per_layer"]) == 75
    assert all(os.path.exists(os.path.join(
        bench_run.LAYERS_DIR, m["name"] + ".py"))
        for m in manifest["per_layer"])
    # its spans and its kernel are those of the reader it stands beside
    assert manifest["per_layer"][-12]["workloads"] == [
        m for m in manifest["per_layer"]
        if m["name"] == "sparse_prefill_ms_per_prefill"][0]["workloads"]


# ---------------------------------------------------------------------------
# a program without the new spans
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", NEW_SPAN_READERS)
def test_the_parents_spans_give_a_new_reader_nothing(name):
    """PR 39's parent: `serving/emit` holds puts and finishes alike and has
    no children; no handler span."""
    read = reader(name)
    assert read(lane_rounds(children=False), trace_of(DEVICE),
                run_facts()) is None
    assert read([], trace_of(DEVICE), run_facts()) is None


def test_the_parents_spans_still_read_prefill_and_other():
    spans, trace, run = (lane_rounds(children=False), trace_of(DEVICE),
                         run_facts())
    assert reader("lane_idle_ms_per_round.prefill_host")(
        spans, trace, run) == pytest.approx(WANT_S["prefill_host"] / 3 * 1e3)
    assert reader("lane_idle_ms_per_round.other")(
        spans, trace, run) == pytest.approx(WANT_S["other"] / 3 * 1e3)


@pytest.mark.parametrize("name", NINE)
def test_spans_older_than_the_phase_spans_read_as_nothing(name):
    # no `decode/*`, no `serving/lane_iter`: the program before PR 24
    old = [span("serving/decode_step", 1.0, 4.0, tokens=2)]
    assert reader(name)(old, trace_of(DEVICE), run_facts()) is None


# ---------------------------------------------------------------------------
# the manifest
# ---------------------------------------------------------------------------

def test_the_nine_are_declared_last_for_the_five_decode_cells():
    manifest = bench_run.load_json(bench_run.MANIFEST)
    # (last but for the six readers PR 42, the five PR 44, the one PR 45,
    # the eight PR 48, the one PR 49, the one PR 50, the three PR 51, the
    # one PR 53, the three PR 54, the one PR 55 and the two PR 56 appended
    # behind them)
    last = manifest["per_layer"][-42:-33]
    assert [m["name"] for m in last] == list(NINE)
    for m in last:
        assert m["workloads"] == DECODE_CELLS, m["name"]
        assert (m["unit"], m["better"], m["moves"]) == \
            ("ms", "lower", "tokens_per_s")
        assert m["layer"] == ("serving front" if m["name"].startswith(
            "token_out") else "scheduler")
        assert m["source"] == ("device_trace" if m["name"].startswith(
            "lane_idle") else "program_span")
        assert os.path.exists(os.path.join(bench_run.LAYERS_DIR,
                                           m["name"] + ".py"))
