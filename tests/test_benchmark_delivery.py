"""`benchmark/delivery.py` and its three readers (`tokens_sent_per_s`,
`client_read_share`, `token_delivery_ms_mean`) on synthetic spans and records,
and their manifest entries (PR 54: what that PR adds under benchmark/ are the
module and the readers; their tests are here, where tier-1 runs)."""

import json
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import delivery                        # noqa: E402
from benchmark import run as bench_run                # noqa: E402

READERS = ("tokens_sent_per_s", "client_read_share", "token_delivery_ms_mean")
W0, SECONDS, PERIOD, TOKENS = 100.0, 45.0, 0.1, 768
WARM = 40                       # what the writer sent before the window


def _pass(t0, tokens, total, **attrs):
    return {"name": "serving/write_pass", "t0": t0, "t1": t0 + 0.002,
            "attrs": dict({"frames": 96, "streams": 96, "enders": 0,
                           "bytes": 96 * 70, "backlogged": 0,
                           "tokens": tokens, "tokens_total": total,
                           "unsent_bytes": 0}, **attrs)}


def _server(seconds=SECONDS + 1.0, period=PERIOD, tokens=TOKENS):
    """A pass of `tokens` every `period`, the first half a period in."""
    out, total = [], WARM
    for k in range(int(seconds / period)):
        total += tokens
        out.append(_pass(W0 + (k + 0.5) * period, tokens, total))
    return out


def _record(times, cancelled=False):
    return types.SimpleNamespace(token_times=list(times),
                                 cancelled=cancelled)


def _clients(passes, lag, streams=4, cancel_from=None):
    """Every pass's tokens stamped `lag(t0)` seconds after the pass began,
    dealt over `streams` records; the records from `cancel_from` on are
    marked cancelled, as the streams the window's end cuts are."""
    recs = [_record([], cancelled=(cancel_from is not None
                                   and i >= cancel_from))
            for i in range(streams)]
    for p in passes:
        n = p["attrs"]["tokens"]
        for i, r in enumerate(recs):
            share = n // streams + (1 if i < n % streams else 0)
            r.token_times.extend([p["t0"] + lag(p["t0"])] * share)
    return recs


def _run(records, **facts):
    return dict({"window": (W0, W0 + SECONDS + 0.7), "seconds": SECONDS,
                 "records": records}, **facts)


def _read_all(spans, run):
    return {name: bench_run.load_reader(name)(spans, None, run)
            for name in READERS}


def _delivery_line(capsys):
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("{")]
    return [ln for ln in lines if ln["phase"].startswith("delivery")]


# the passes inside [W0, W0 + SECONDS]: 450 of them
SENT = int(SECONDS / PERIOD) * TOKENS


def _keeps_up():
    passes = _server()
    return passes, _clients(passes, lambda t: 0.004), {
        "tokens_sent_per_s": SENT / SECONDS, "client_read_share": 100.0,
        "token_delivery_ms_mean": 4.0}


def _drains_at_95():
    """A client that reads 0.95 of what the server sends, from the window's
    start: the token the server sent at w0 + s is stamped at w0 + s / 0.95.
    S - R grows as 0.05 r t; the area is the triangle 0.05 r T^2 / 2, the
    tokens read 0.95 r T: the mean lag T x 0.05 / (2 x 0.95)."""
    passes = _server(period=0.01, tokens=64)
    sent = int(round(SECONDS / 0.01)) * 64
    return passes, _clients(passes, lambda t: (t - W0) * (1 / 0.95 - 1)), {
        "tokens_sent_per_s": sent / SECONDS, "client_read_share": 95.0,
        "token_delivery_ms_mean": 1e3 * SECONDS * 0.05 / (2 * 0.95)}


def _cancelled_count():
    """Half of the records are cancelled ones (the window's end cut their
    streams): the server sent their tokens, so they count as read."""
    passes = _server()
    return passes, _clients(passes, lambda t: 0.004, cancel_from=2), {
        "tokens_sent_per_s": SENT / SECONDS, "client_read_share": 100.0,
        "token_delivery_ms_mean": 4.0}


def _a_pass_in_flight_at_the_end():
    """The last pass of the window is stamped after its end: the share is
    one pass short of 100."""
    passes = _server()
    late = max(p["t0"] for p in passes if p["t0"] <= W0 + SECONDS)
    return passes, _clients(
        passes, lambda t: 0.2 if t == late else 0.004), {
        "tokens_sent_per_s": SENT / SECONDS,
        "client_read_share": 100.0 * (SENT - TOKENS) / SENT,
        "token_delivery_ms_mean": 1e3 * (
            (SENT - TOKENS) * 0.004 + TOKENS * PERIOD / 2)
        / (SENT - TOKENS)}


def _the_client_ahead_of_the_server():
    """Stamps a second BEFORE the passes that sent them: not one clock, or
    spans lost.  No reading."""
    passes = _server()
    return passes, _clients(passes, lambda t: -1.0), dict.fromkeys(READERS)


def _the_parents_program():
    """Passes as every program before PR 54 stamps them: no count."""
    passes = _server()
    records = _clients(passes, lambda t: 0.004)
    for p in passes:
        for k in ("tokens", "tokens_total", "unsent_bytes"):
            del p["attrs"][k]
    return passes, records, dict.fromkeys(READERS)


def _no_pass_at_all():
    return [{"name": "serving/decode_step", "t0": W0 + 1.0, "t1": W0 + 1.1,
             "attrs": {"tokens": 768, "trips": 8}}], \
        [_record([W0 + 1.2] * 768)], dict.fromkeys(READERS)


@pytest.mark.parametrize("case", [
    _keeps_up, _drains_at_95, _cancelled_count, _a_pass_in_flight_at_the_end,
    _the_client_ahead_of_the_server, _the_parents_program, _no_pass_at_all],
    ids=lambda f: f.__name__.strip("_"))
def test_the_three_readers(case, capsys):
    spans, records, want = case()
    got = _read_all(spans, _run(records))
    for name in READERS:
        if want[name] is None:
            assert got[name] is None, (name, got)
        else:
            assert got[name] == pytest.approx(want[name], rel=2e-3), name
    assert all(v is None for v in got.values()) or \
        got["client_read_share"] <= 100.0


def test_a_client_that_keeps_up_reads_under_a_pass(capsys):
    spans, records, _ = _keeps_up()
    got = _read_all(spans, _run(records))
    assert got["client_read_share"] >= 99.0
    assert got["token_delivery_ms_mean"] < PERIOD * 1e3


def test_the_reduction_is_made_once_a_run_and_logged_once(capsys):
    spans, records, _ = _drains_at_95()
    run = _run(records)
    _read_all(spans, run)
    _read_all(spans, run)
    (line,) = _delivery_line(capsys)
    assert line["phase"] == "delivery"
    assert run["delivery"] is delivery.curves(spans, run)
    # ten readings of each curve; S - R grows tenth by tenth
    behind = [s - r for s, r in zip(line["sent_by_tenth"],
                                    line["read_by_tenth"])]
    assert len(behind) == 10 and behind == sorted(behind)
    assert behind[-1] == line["sent_minus_read_at_end"] == \
        line["sent"] - line["read"]
    assert line["sent_by_tenth"][-1] == line["sent"]
    assert behind[-1] == pytest.approx(0.05 * line["sent"], rel=1e-2)
    # what went out before the window is not the window's
    assert line["sent_before_window"] == WARM


def test_the_lanes_own_count_stands_beside_the_writers(capsys):
    """`lane_tokens`: the window's dispatches' tokens plus a token a
    prefill that did not fail."""
    spans, records, _ = _keeps_up()
    spans = spans + [
        {"name": "serving/decode_step", "t0": W0 + k * PERIOD,
         "t1": W0 + (k + 0.45) * PERIOD, "attrs": {"tokens": 760,
                                                   "trips": 8}}
        for k in range(int(SECONDS / PERIOD))] + [
        {"name": "serving/prefill_compute", "t0": W0 + 1.0 + k,
         "t1": W0 + 1.01 + k, "attrs": {"prompt": 64, "ahead": 0}}
        for k in range(8)] + [
        {"name": "serving/prefill_compute", "t0": W0 + 20.0, "t1": W0 + 20.1,
         "attrs": {"prompt": 64, "error": "ValueError"}}]
    _read_all(spans, _run(records))
    (line,) = _delivery_line(capsys)
    assert line["lane_tokens"] == int(SECONDS / PERIOD) * 760 + 8
    assert line["passes"] == int(SECONDS / PERIOD)
    assert line["largest_pass_tokens"] == TOKENS


def test_an_inconsistent_run_says_so_and_reads_nothing(capsys):
    spans, records, _ = _the_client_ahead_of_the_server()
    run = _run(records)
    assert _read_all(spans, run) == dict.fromkeys(READERS)
    (line,) = _delivery_line(capsys)
    assert line["phase"] == "delivery_inconsistent"
    assert line["read_ahead_of_sent"] > line["largest_pass_tokens"] == TOKENS
    assert run["delivery"] is None


def test_a_stamp_within_a_pass_of_its_pass_is_no_fault(capsys):
    """Clocks a millisecond apart put a pass's stamps ahead of its step:
    under one pass's tokens that is tolerated."""
    spans, records, _ = _keeps_up()
    records = _clients(spans, lambda t: -0.001)
    got = _read_all(spans, _run(records))
    assert got["tokens_sent_per_s"] == pytest.approx(SENT / SECONDS)
    assert got["client_read_share"] == pytest.approx(100.0)
    assert _delivery_line(capsys)[0]["phase"] == "delivery"


def test_a_held_frames_tokens_step_where_the_pass_finished_them(capsys):
    """A pass that encoded frames and sent none (`tokens` 0, bytes owed),
    then the pass that finished them: S steps at the second."""
    spans = [_pass(W0 + 1.0, 0, WARM, backlogged=1, unsent_bytes=5000),
             _pass(W0 + 3.0, 500, WARM + 500, frames=0)]
    got = _read_all(spans, _run([_record([W0 + 3.01] * 500)]))
    assert got["tokens_sent_per_s"] == pytest.approx(500 / SECONDS)
    assert got["token_delivery_ms_mean"] == pytest.approx(10.0)
    (line,) = _delivery_line(capsys)
    assert line["unsent_bytes_max"] == 5000
    assert line["sent_by_tenth"][0] == 500


@pytest.mark.parametrize("name,layer,unit,better", [
    ("tokens_sent_per_s", "serving front", "tokens/s", "higher"),
    ("client_read_share", "load generator", "%", "higher"),
    ("token_delivery_ms_mean", "load generator", "ms", "lower")])
def test_the_three_are_declared_last_for_the_nine_decode_cells(
        name, layer, unit, better):
    manifest = bench_run.load_json(bench_run.MANIFEST)
    e2e, = [m for m in manifest["end_to_end"] if m["name"] == "tokens_per_s"]
    # (PR 56's cell is the tenth, and its two readers stand behind PR 55's)
    assert len(e2e["workloads"]) == 10
    assert [m["name"] for m in manifest["per_layer"][-7:-4]] == list(READERS)
    (m,) = [m for m in manifest["per_layer"] if m["name"] == name]
    assert m == {"name": name, "unit": unit, "better": better,
                 "source": "program_counter", "layer": layer,
                 "moves": "tokens_per_s", "workloads": e2e["workloads"]}
    assert os.path.exists(os.path.join(bench_run.LAYERS_DIR, name + ".py"))


@pytest.mark.parametrize("cell,listed", [
    ("mimov2flash_reasoning_decode", True), ("gpt2s_decode_saturated", True),
    ("minicpmsala_longdoc_mixed", True), ("resnet50_feed_b256", False),
    ("resnet50_dp4_loop_b1024", False)])
def test_the_harness_finds_the_readers_in_the_decode_cells(cell, listed):
    manifest = bench_run.load_json(bench_run.MANIFEST)
    names = {m["name"] for m in bench_run.resolve_cell(manifest, cell)[4]}
    assert (set(READERS) <= names) == listed
    assert listed or not set(READERS) & names


# --- the cut of `run["host_spans"]` that stands in for a repair of
# `xplane.Trace.breakdown` (delivery._trim_host_spans)

def _trace_and_host_spans(seed):
    """A device line of 4,000 operations with a hole after most of them,
    and the host spans of a whole window around a sub-window of 3 s."""
    import random
    from benchmark import xplane
    rng = random.Random(seed)
    ops, t = [], 10.0
    for i in range(4000):
        d = rng.uniform(2e-4, 1e-3)
        ops.append(("op.%d" % (i % 7), t, t + d))
        t += d + rng.choice((0.0, 1e-6, 3e-4))
    host, at = [], 0.0
    while at < 45.0:
        d = rng.uniform(0.004, 0.02)
        host.append((rng.choice(("serving/decode_step",
                                 "serving/prefill_compute")), at, at + d))
        at += d + rng.uniform(0.0, 0.003)
    return xplane.Trace({0: ops}), host


@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize("program", ["parent", "change"])
def test_breakdown_is_the_same_object_after_the_cut(seed, program, capsys):
    """On the parent's program (no `tokens`: the readers give None) and on
    the change's, the first reader cuts the list, and `breakdown` of the
    cut list is `breakdown` of the whole one, bit for bit."""
    trace, host = _trace_and_host_spans(seed)
    w0, w1 = 10.4, 13.4
    spans = _server()
    records = _clients(spans, lambda t: 0.004)
    if program == "parent":
        for s in spans:
            for k in ("tokens", "tokens_total", "unsent_bytes"):
                del s["attrs"][k]
    run = _run(records, host_spans=list(host), trace_window=(w0, w1))
    whole = trace.breakdown(w0, w1, host)
    got = _read_all(spans, run)
    assert all((v is None) == (program == "parent") for v in got.values())
    assert 0 < len(run["host_spans"]) < len(host) / 5
    assert trace.breakdown(w0, w1, run["host_spans"]) == whole
    assert len(whole["idle_gaps"]) >= 2
    (line,) = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
               if "host_spans_trimmed" in ln]
    assert (line["had"], line["kept"]) == (len(host), len(run["host_spans"]))


@pytest.mark.parametrize("facts", [
    {}, {"host_spans": [("serving/decode_step", 1.0, 2.0)]},
    {"trace_window": (1.0, 2.0)},
    {"host_spans": [("serving/decode_step", 1.0, 2.0)],
     "trace_window": (0.5, 1.5)}])
def test_a_run_with_nothing_to_cut_is_left_as_it_is(facts, capsys):
    run = _run([], **facts)
    before = dict(run)
    assert delivery.curves([], run) is None
    assert {k: run[k] for k in before} == before
    assert "host_spans_trimmed" not in capsys.readouterr().out
