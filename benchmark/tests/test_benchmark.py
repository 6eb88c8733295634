"""Tests of the benchmark's own yardstick, on the CPU.

    python -m pytest benchmark/tests -q -m 'not slow'     # seconds each
    python -m pytest benchmark/tests -q -m slow           # cell rehearsals
"""

import json
import os
import re
import shutil
import statistics
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from benchmark import costs, loadgen, peaks, stats, xplane
from benchmark import run as bench_run

ROOT = bench_run.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    return bench_run.load_json(bench_run.MANIFEST)


# ---------------------------------------------------------------------------
# the manifest and what it names
# ---------------------------------------------------------------------------

def test_manifest_keys_and_limits(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(bench_run.MANIFEST) <= 64 * 1024
    assert 1 <= manifest["run_seconds"] <= 51
    cells = len(manifest["workloads"])
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) \
        <= max(cells // 4, 1)
    # a full check with the full 24 cells fits the driver's 43200 s
    runs = 2 + 14 * 24
    assert runs * (manifest["run_seconds"] + 60) + 24 * 2 * 90 + 1200 \
        <= 43200
    for word in manifest["command"]:
        assert not word.startswith("/") and ".." not in word


def test_names_units_and_sources(manifest):
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in manifest[group]:
            assert NAME.match(e["name"]), e["name"]
            names.append((group in ("end_to_end", "per_layer"), e["name"]))
    assert len(names) == len(set(names))
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in manifest["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in manifest["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["traffic"]) and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    pairs = [(w["config"], w["traffic"]) for w in manifest["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert "setup_s" in [m["name"] for m in manifest["end_to_end"]]


def test_every_cell_resolves_by_name(manifest):
    e2e_names = {m["name"] for m in manifest["end_to_end"]}
    used = set()
    for w in manifest["workloads"]:
        cell, config, traffic, e2e, per_layer = bench_run.resolve_cell(
            manifest, w["name"])
        used.add(w["config"])
        assert config["name"] == w["config"] and traffic["why"]
        assert os.path.exists(os.path.join(
            bench_run.HERE, "reference", config["reference"] + ".py"))
        assert os.path.exists(os.path.join(
            bench_run.HERE, "drivers", config["driver"] + ".py"))
        mine = {m["name"] for m in e2e}
        assert "setup_s" in mine and len(mine) >= 2 and per_layer
        for m in per_layer:
            assert callable(bench_run.load_reader(m["name"]))
            assert m["moves"] in mine, (m["name"], w["name"])
            assert m["moves"] in e2e_names
    assert used == {c["name"] for c in manifest["configs"]}
    for c in manifest["configs"]:
        assert c["file"].startswith(tuple(p + "/" for p in manifest["paths"]))
        assert bench_run.load_json(os.path.join(ROOT, c["file"]))[
            "reduced"] == c["reduced"]


def test_new_cell_traffic_and_reader_are_found_with_no_edit(
        manifest, tmp_path, monkeypatch):
    """What a later PR does: add files and manifest entries, edit none."""
    copy = tmp_path / "benchmark"
    shutil.copytree(bench_run.HERE, copy,
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    (copy / "configs" / "dummy_model.json").write_text(json.dumps(
        {"name": "dummy_model", "driver": "train_fluid",
         "reference": "resnet50_imagenet", "reduced": []}))
    (copy / "traffic" / "dummy_mix.json").write_text(json.dumps(
        {"loop": "feed", "why": "a test"}))
    (copy / "layers" / "dummy.metric.py").write_text(
        "def read(spans, trace, run):\n    return 42.0\n")
    man = json.loads(json.dumps(manifest))
    man["configs"].append({"name": "dummy_model", "source": "a test",
                           "file": "benchmark/configs/dummy_model.json",
                           "reduced": [], "why": "a test"})
    man["workloads"].append({"name": "dummy_cell", "config": "dummy_model",
                             "traffic": "dummy_mix", "chips": 1,
                             "why": "a test"})
    man["per_layer"].append({"name": "dummy.metric", "unit": "ms",
                             "better": "lower", "source": "program_span",
                             "layer": "a test", "moves": "setup_s",
                             "workloads": ["dummy_cell"]})
    monkeypatch.setattr(bench_run, "ROOT", str(tmp_path))
    monkeypatch.setattr(bench_run, "TRAFFIC_DIR", str(copy / "traffic"))
    monkeypatch.setattr(bench_run, "LAYERS_DIR", str(copy / "layers"))
    cell, config, traffic, e2e, per_layer = bench_run.resolve_cell(
        man, "dummy_cell")
    assert config["name"] == "dummy_model" and traffic["loop"] == "feed"
    assert [m["name"] for m in e2e] == ["setup_s"]
    assert [m["name"] for m in per_layer] == ["dummy.metric"]
    assert bench_run.load_reader("dummy.metric")([], None, {}) == 42.0
    # and an old cell is untouched by the additions
    assert bench_run.resolve_cell(man, "resnet50_feed_b256")[4] == \
        bench_run.resolve_cell(manifest, "resnet50_feed_b256")[4]


def test_no_tpu_means_exit_3_and_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join("benchmark", "run.py"), "--workload",
         "resnet50_feed_b256", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=120)
    assert p.returncode == 3
    assert not [ln for ln in p.stdout.splitlines() if '"metrics"' in ln]
    assert "no tpu" in p.stderr


# ---------------------------------------------------------------------------
# the generator
# ---------------------------------------------------------------------------

CHAT = {"rate_per_s": 5.0,
        "prompt_tokens": {"kind": "lognormal", "median": 192, "sigma": 0.8,
                          "min": 16, "max": 896},
        "output_tokens": {"kind": "lognormal", "median": 64, "sigma": 0.6,
                          "min": 8, "max": 160}}


@pytest.mark.parametrize("seed", [0, 7, 2 ** 31 + 12345])
def test_traffic_is_a_function_of_the_seed_alone(seed):
    a = loadgen.make_requests(CHAT, seed, 200, 50257)
    b = loadgen.make_requests(CHAT, seed, 200, 50257)
    assert all(np.array_equal(x["prompt"], y["prompt"])
               and x["max_new"] == y["max_new"] for x, y in zip(a, b))
    assert loadgen.due_times(CHAT, seed, 40) == \
        loadgen.due_times(CHAT, seed, 40)
    assert all(x["prompt"].min() >= 1 for x in a)      # 0 is eos


def test_every_seed_offers_the_same_work_in_another_order():
    a = loadgen.make_requests(CHAT, 1, 200, 50257)
    b = loadgen.make_requests(CHAT, 2, 200, 50257)
    assert sorted(len(r["prompt"]) for r in a) == \
        sorted(len(r["prompt"]) for r in b)
    assert sorted(r["max_new"] for r in a) == sorted(r["max_new"] for r in b)
    assert [len(r["prompt"]) for r in a] != [len(r["prompt"]) for r in b]
    da, db = loadgen.due_times(CHAT, 1, 40), loadgen.due_times(CHAT, 2, 40)
    assert len(da) == len(db) == 200 and da != db
    gaps = lambda d: sorted(np.diff(d).round(9))       # noqa: E731
    # the same gaps but for the one that falls after the last request
    assert len(set(gaps(da)) ^ set(gaps(db))) <= 2
    assert abs(da[-1] - 40.0) < 2.0 and abs(db[-1] - 40.0) < 2.0


def test_quantile_lengths():
    v = loadgen.quantile_values(CHAT["prompt_tokens"], 1001)
    assert v == sorted(v) and v[0] == 16 and v[-1] == 896
    assert v[500] == 192                               # the median
    assert loadgen.quantile_values({"kind": "fixed", "value": 128}, 3) == \
        [128] * 3
    u = loadgen.quantile_values({"kind": "uniform", "min": 32, "max": 64}, 64)
    assert u[0] == 32 and u[-1] == 64 and statistics.mean(u) == 48
    g = loadgen.exponential_gaps(5.0, 2000)
    assert abs(sum(g) - 2000 / 5.0) / 400 < 0.01       # mean gap 1/rate


# ---------------------------------------------------------------------------
# percentiles, spreads, intervals
# ---------------------------------------------------------------------------

def test_percentile_on_known_samples():
    xs = list(range(1, 101))
    assert stats.percentile(xs, 50) == 50.5
    assert stats.percentile(xs, 95) == pytest.approx(95.05)
    assert stats.percentile(xs, 0) == 1 and stats.percentile(xs, 100) == 100
    assert stats.percentile([3.0], 95) == 3.0
    assert stats.percentile([1, 2, 3, 4], 95) == \
        pytest.approx(float(np.percentile([1, 2, 3, 4], 95)))
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_iqr_spread_is_the_contracts():
    xs = [100.0, 101.0, 99.0, 100.5, 99.5, 102.0]
    q1, _, q3 = statistics.quantiles(xs, n=4)
    assert stats.iqr_spread(xs) == (q3 - q1) / statistics.median(xs)


def test_interval_arithmetic():
    iv = [(0, 2), (1, 3), (5, 6), (6, 7), (10, 10)]
    assert stats.union_seconds(iv) == 5
    assert stats.merge_intervals(iv) == [(0, 3), (5, 7)]
    assert stats.gaps(stats.merge_intervals(iv), -1, 8) == \
        [(-1, 0), (3, 5), (7, 8)]


# ---------------------------------------------------------------------------
# the trace reduction: arithmetic on hand-written intervals ...
# ---------------------------------------------------------------------------

def hand_trace():
    dev0 = [("fusion.1", 0.0, 4.0), ("all-reduce.7", 3.0, 6.0),
            ("fusion.2", 8.0, 9.0), ("fusion.1", 9.0, 10.0)]
    dev1 = [("fusion.1", 0.0, 5.0), ("all-reduce.7", 5.0, 6.0)]
    t = xplane.Trace({0: dev0, 1: dev1},
                     modules=[("jit_bench_anchor(1)", 0.5, 1.0),
                              ("jit_step(2)", 1.0, 9.0),
                              ("jit_bench_anchor(1)", 9.5, 10.0)])
    # the second anchor returned 0.2 s after its module ended (it waited),
    # the first 0.0 s after: the tighter one ties the clocks
    t.set_anchor([(1000.0, 50.0), (1009.2, 59.2)])
    return t


def test_busy_idle_per_op_and_exposed_collectives():
    t = hand_trace()
    assert t.busy(0, 10) == {0: 8.0, 1: 6.0}
    assert t.busy_mean(0, 10) == 7.0                   # idle share 30%
    assert t.busy(2, 5) == {0: 3.0, 1: 3.0}            # clipped to a window
    ops = t.op_seconds(0, 10)
    assert ops["fusion.1"] == (5.0 + 5.0) / 2
    assert ops["all-reduce.7"] == (3.0 + 1.0) / 2
    # device 0: all-reduce 3..6, covered by fusion.1 until 4 -> 2 exposed;
    # device 1: 1 exposed
    assert t.exposed_seconds(0, 10) == 1.5
    assert t.matching_seconds(0, 10, lambda n: n == "fusion.2") == 0.5
    assert t.idle_gaps(0, 10) == [(6.0, 8.0)]


def test_containers_are_not_work():
    """A `lax.fori_loop` is ONE `while` event around its inner operations
    (the four-chip cell's ten steps a call); counted as work it makes the
    device busy throughout and hides every collective inside it."""
    ops = [("while.9", 0.0, 10.0), ("fusion.1", 0.5, 3.0),
           ("all-reduce-start.2", 3.0, 3.1), ("fusion.3", 3.5, 5.0),
           ("conditional.4", 5.0, 7.0), ("fusion.5", 5.5, 6.5),
           ("all-reduce-done.2", 7.0, 8.0), ("fusion.1", 8.0, 9.5),
           ("fusion.6", 11.0, 12.0)]
    work, containers = xplane.drop_containers(ops)
    assert [n for n, _, _ in containers] == ["while.9", "conditional.4"]
    assert len(work) == 7
    # the exchange is in flight from its start to its done on the async line
    t = xplane.Trace({0: ops}, async_ops={
        0: [("all-reduce-start.2", 3.0, 8.0), ("copy-start.1", 0.0, 9.0)]})
    assert [n for n, _, _ in t.containers[0]] == ["while.9", "conditional.4"]
    assert t.busy(0, 12) == {0: 2.5 + 0.1 + 1.5 + 1.0 + 1.0 + 1.5 + 1.0}
    assert "while.9" not in t.op_seconds(0, 12)
    # in flight 3..8; fusion.3 hides 3.5..5, fusion.5 hides 5.5..6.5
    assert t.exposed_seconds(0, 12) == pytest.approx(5.0 - 1.5 - 1.0)
    assert (9.5, 11.0) in t.idle_gaps(0, 12)
    assert (0.0, 0.5) in t.idle_gaps(0, 12)            # not hidden by while
    # events that only touch or overlap in part are both work
    work, containers = xplane.drop_containers(
        [("a", 0.0, 1.0), ("b", 1.0, 2.0), ("c", 1.5, 2.5)])
    assert len(work) == 3 and not containers


def test_breakdown_names_gaps_by_host_span_and_clocks_line_up():
    t = hand_trace()
    b = t.breakdown(0, 10, spans=[("bench/call", 5.5, 7.5),
                                  ("bench/other", 7.5, 9.0)], top=3)
    assert b["device_ops"][0] == ["fusion.1", 5.0]
    assert len(b["device_ops"]) == 3
    assert b["idle_gaps"] == [["bench/call", 2.0]]
    assert t.from_monotonic(52.0) == 3.0 and t.from_wall(1000.5) == 1.5


# ... and the reading, on a small trace recorded on a v5e chip

RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "tiny_tpu.xplane.pb")
STAMPS = os.path.join(os.path.dirname(__file__), "data", "tiny_tpu.json")


def test_recorded_tpu_trace_reads():
    """benchmark/tests/record_trace.py on one v5e: four calls of a small
    matmul inside the benchmark's profiled window, host tracer off, the
    device-side anchor at both ends."""
    t = xplane.read_trace(RECORDED)
    host = bench_run.load_json(STAMPS)
    assert sorted(t.device_ops) == [0]
    t.set_anchor([tuple(a) for a in host["anchors"]])
    w0, w1 = (t.from_monotonic(x) for x in host["window"])
    assert 0.01 < w1 - w0 < 0.02                       # cut by close()
    busy = t.busy_mean(w0, w1)
    assert 0.0 < busy < 0.5 * (w1 - w0)                # mostly idle
    # the four calls' device work, each inside its host call to within the
    # anchor's slack (a dispatch latency, well under a millisecond)
    calls = [(t.from_monotonic(a), t.from_monotonic(b))
             for a, b in host["calls"]]
    mods = [(s, e) for n, s, e in t.modules if n.startswith("jit__lambda")]
    assert len(mods) == 4
    for (cs, ce), (ms, me) in zip(calls, mods):
        assert cs - 1e-3 <= ms and me <= ce + 1e-3
    assert len([g for g in t.idle_gaps(calls[0][0], calls[-1][1])
                if g[1] - g[0] > 1e-3]) >= 3           # the sleeps
    ops = t.op_seconds(w0, w1)
    assert "fusion" in ops and "fusion(" in t.text("fusion")
    assert sum(ops.values()) >= busy * 0.999
    with pytest.raises(RuntimeError):
        t.set_anchor([(0.0, 0.0)])                     # one stamp, two anchors


def test_recorded_four_chip_loop_slice():
    """0.16 s of the four-chip cell's traced run on the v5e (chips 0 and 1;
    benchmark/tests/dump_trace.py): the end of one `run_loop` call - a
    `while` of nine steps behind one peeled step - the host's gap before the
    next call, and that call's peeled step, with 100 all-reduces a step on
    the core's own line."""
    from benchmark.tests.dump_trace import load_events
    t, facts = load_events(os.path.join(os.path.dirname(__file__), "data",
                                        "dp4_loop_slice.npz"))
    w0, w1 = facts["window"]
    assert sorted(t.device_ops) == [0, 1]
    for dev in (0, 1):
        (name, s, e), = t.containers[dev]
        assert name.startswith("while") and s < w0 < e < w1
        assert not [n for n, _, _ in t.device_ops[dev]
                    if n.startswith(("while", "conditional", "call"))]
    busy = t.busy_mean(w0, w1)
    assert 0.93 * (w1 - w0) < busy < 0.97 * (w1 - w0)      # idle 3-7%
    # the longest gap is the host's, between the two calls (7 ms)
    g0, g1 = t.idle_gaps(w0, w1)[0]
    assert 0.006 < g1 - g0 < 0.008
    assert t.containers[0][0][2] < g0      # after the loop and its epilogue
    b = t.breakdown(w0, w1, [("bench/train_call", -1.0, (g0 + g1) / 2),
                             ("bench/train_call", (g0 + g1) / 2, 2.0)])
    assert b["idle_gaps"][0][0] == "bench/train_call"
    assert not b["device_ops"][0][0].startswith("while")
    # synchronous all-reduces: what they take on the line is exposed
    ar = [(s, e) for n, s, e in t.device_ops[0]
          if n.startswith("all-reduce") and s >= w0 and e <= w1]
    assert 140 <= len(ar) <= 170                           # ~1.5 steps
    exposed = t.exposed_seconds(w0, w1)
    assert 0.0015 < exposed < 0.003
    assert exposed == pytest.approx(sum(e - s for s, e in ar), rel=0.05)
    # with the container counted as work the loop hides every one of them
    flat = xplane.Trace.__new__(xplane.Trace)
    flat.device_ops = {d: t.device_ops[d] + t.containers[d]
                       for d in t.device_ops}
    flat.async_ops = {}
    assert flat.exposed_seconds(w0, w1) < 0.4 * exposed


# ---------------------------------------------------------------------------
# peaks and costs
# ---------------------------------------------------------------------------

def test_peaks_table_and_kernel_cost():
    pk = peaks.peaks_for("TPU v5 lite")
    assert pk["flops_per_s"]["bfloat16"] == 197e12
    assert pk["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v99")
    flops, bytes_ = costs.decode_attention_cost([100, 28], 12, 64)
    assert flops == 4 * 128 * 12 * 64
    assert bytes_ == 2 * 128 * 12 * 64 * 4 + 2 * 12 * 64 * 8
    least, bound = costs.roofline_seconds(flops, bytes_, 197e12, 819e9)
    assert bound == "memory" and least == bytes_ / 819e9


# ---------------------------------------------------------------------------
# each plain reference against the program, tiny width, on the CPU
# ---------------------------------------------------------------------------

class _Ctx(object):
    def __init__(self, **kw):
        self.__dict__.update(kw)
        self.logged = []

    def log(self, **fields):
        self.logged.append(fields)


def test_gpt2_reference_matches_prefill_plus_decode_through_the_cache(
        tmp_path):
    from benchmark.drivers import serve_decode
    from benchmark.reference import gpt2_small
    from paddle_tpu.inference.decode import (GenerativePredictor,
                                             save_decode_model)
    meta = {"vocab_size": 97, "d_model": 32, "n_heads": 4, "n_layers": 2,
            "max_seq_len": 128, "eos_id": 0,
            "prefill_buckets": [16, 32, 64, 128]}
    state = serve_decode.make_state_on_device(meta, 2 ** 31 + 5)
    art = save_decode_model(str(tmp_path / "lm"),
                            {n: np.asarray(v) for n, v in state.items()},
                            meta)
    pred = GenerativePredictor(art)
    ctx = _Ctx(seed=11, reference=gpt2_small, config={
        "reference_check": {"prompt_tokens": [5, 20, 40], "steps": 5},
        # fp32 on the CPU: both sides agree to rounding
        "tolerances": {"logits": 1e-4, "top1_gap": 1e-4}})
    assert serve_decode.check_against_reference(ctx, pred, state, meta)
    assert ctx.logged[-1]["buckets"] == [16, 32, 64]
    # and the check can fail: a reference with one layer fewer
    ctx.reference = type("Short", (), {"forward": staticmethod(
        lambda st, t, L, H: gpt2_small.forward(st, t, L - 1, H))})
    assert not serve_decode.check_against_reference(ctx, pred, state, meta)


def test_resnet_reference_matches_the_program_loss_and_update():
    import paddle_tpu.fluid as fluid
    from benchmark.drivers import train_fluid
    from benchmark.reference import resnet50_imagenet
    from paddle_tpu.fluid import functionalizer
    from paddle_tpu.models import resnet
    opt = {"lr": 0.01, "momentum": 0.9, "l2_decay": 1e-4}
    main, startup, _, loss, _, _ = resnet.get_model(
        batch_size=8, class_dim=10, depth=50, layout="NHWC", lr=opt["lr"])
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        fluid.Executor(fluid.TPUPlace(0)).run(startup)
        params = [p.name for p in main.global_block().all_parameters()]
        persist = [n for n in functionalizer.persistable_names(main)
                   if scope.get(n) is not None]
        before = np.asarray(scope.get(params[0])).copy()
        train_fluid.reseed_weights(scope, params, 2 ** 31 + 9)
        after = np.asarray(scope.get(params[0]))
        assert not np.allclose(before, after)
        assert abs(after.std() / before.std() - 1.0) < 0.1
        stepper = train_fluid._Stepper(fluid, main, loss, scope, 1)
        sample = train_fluid.make_batches(3, 1, 8, 64, 10)[0]
        ctx = _Ctx(reference=resnet50_imagenet, config={
            "reference_stages": [3, 4, 6, 3],
            # fp32 on the CPU, batch 8 at 64x64 (the last stage's BN sees 32
            # values a channel): loss to 1e-3, updates to a few percent
            "tolerances": {"loss": 2e-3, "update": 0.08,
                           "norm_ratio": [0.9, 1.1]}})
        ok, facts = train_fluid.check_against_reference(
            ctx, stepper, scope, params, persist, sample, opt)
        assert ok, facts
        loss_first = facts["loss_program"]
        # and the check can fail: a gradient without the decay term on a
        # tensor whose gradient is small beside decay * weight
        ok, facts = train_fluid.check_against_reference(
            ctx, stepper, scope, params, persist, sample,
            dict(opt, lr=opt["lr"] * 2))
        assert not ok
        # under AMP the first step is held twice: as configured (bf16: loss,
        # head and sizes) and in fp32 at "highest" (every named update by
        # direction), and AMP is on again afterwards
        fluid.set_amp(True)
        try:
            # (bf16 at b8/64 px is far rougher than at the cell's size)
            ctx.config["tolerances"] = {"loss": 0.6, "update": 0.6,
                                        "fp32_loss": 1e-3,
                                        "fp32_update": 0.1}
            ok, facts = train_fluid.check_against_reference(
                ctx, stepper, scope, params, persist, sample, opt)
            assert ok and fluid.amp_enabled(), facts
            assert facts["update_rel_err"]["first_conv"] > 0.5   # bf16
            assert facts["fp32_update_rel_err"]["first_conv"] < 0.1
            assert facts["fp32_update_rel_err"]["last_fc"] < 1e-2
            assert abs(facts["fp32_loss_program"]
                       - facts["loss_reference"]) < 1e-3
        finally:
            fluid.set_amp(False)
        # the state was put back every time: the same step gives the same
        # loss again
        assert stepper.step(sample) == pytest.approx(loss_first, abs=1e-5)


# ---------------------------------------------------------------------------
# a run names its own stall
# ---------------------------------------------------------------------------

class _Chip(object):
    """A device whose `memory_stats()` takes `held` seconds at its
    `at`-th reading: what a pause of the process looks like to the
    memory thread."""

    def __init__(self, at=None, held=0.0):
        self.calls, self.at, self.held = 0, at, held

    def memory_stats(self):
        self.calls += 1
        if self.calls == self.at:
            time.sleep(self.held)
        return {"bytes_in_use": 100 + self.calls, "bytes_reserved": 7}


@pytest.mark.parametrize("case,chip,paused", [
    ("clean", _Chip(), False), ("held_for_a_second", _Chip(3, 1.0), True)])
def test_the_memory_thread_says_whether_the_process_paused(case, chip,
                                                           paused):
    watch = bench_run.MemoryWatch([chip])
    watch.start()
    time.sleep(2.1)
    watch.stop()
    t0 = watch.first
    said = watch.stall(t0)
    assert said["readings"] == watch.samples == chip.calls
    assert watch.peak == 100 + chip.calls + 7
    if paused:
        # the third reading began 0.5 s in and was held for a second
        assert said["longest_gap_s"] >= 1.0
        assert 1.4 <= said["longest_gap_at_s"] <= 2.0
        assert said["readings"] <= said["readings_if_clean"] - 3
    else:
        assert 0.25 <= said["longest_gap_s"] < 0.5
        assert abs(said["readings"] - said["readings_if_clean"]) <= 1


# ---------------------------------------------------------------------------
# the generator's process against a tiny served model
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """(generator, server, model name): a two-slot lane of a tiny decode
    model behind the wire, and ONE generator's process for the module."""
    from paddle_tpu.inference.decode import build_tiny_decode_model
    from paddle_tpu.serving.server import InferenceServer
    art = str(tmp_path_factory.mktemp("loadgen_model") / "lm")
    build_tiny_decode_model(art, vocab_size=32, d_model=16, n_heads=2,
                            n_layers=2, max_seq_len=64, eos_id=0, seed=7)
    gen = loadgen.Generator()
    srv = InferenceServer("127.0.0.1:0").start()
    try:
        srv.registry.load_model("lm", art, decode_slots=2)
        yield gen.serve(srv.endpoint), srv, "lm"
    finally:
        rc = gen.close()
        srv.shutdown(drain=False, timeout=10.0)
    assert rc == 0


def tiny_requests(n, max_new=6):
    mix = {"prompt_tokens": {"kind": "uniform", "min": 3, "max": 12},
           "output_tokens": {"kind": "fixed", "value": max_new}}
    return loadgen.make_requests(mix, 2 ** 31 + 9, n, 32)


def whole(rec, requests):
    """A record came back as the child wrote it: stamps in order on this
    process's clock, one stamp a token, the request's own sizes."""
    req = requests[rec.index % len(requests)]
    assert rec.prompt_len == len(req["prompt"])
    assert rec.max_new == req["max_new"]
    assert len(rec.tokens) == len(rec.token_times)
    stamps = [rec.sent] + rec.token_times + [rec.done]
    assert stamps == sorted(stamps)
    return True


def test_closed_loop_runs_in_the_child_and_marks_what_it_cancelled(served):
    gen, srv, name = served
    requests = tiny_requests(8)
    before = time.monotonic()
    t0, recs = loadgen.run_closed_loop(gen, name, requests, 4, 3.0)
    after = time.monotonic()
    # one clock: the child's stamps lie inside this process's bracket
    assert before <= t0 <= after
    assert all(before <= r.sent and r.done <= after for r in recs)
    assert gen.stats["clock_round_trip_ms"] < 1e3
    assert [r.index for r in recs] == list(range(len(recs)))
    assert all(whole(r, requests) for r in recs)
    judged = [r for r in recs if not r.cancelled]
    assert len(judged) >= 4 and all(r.ok(0, 64) for r in judged)
    # every stream that was in flight at the window's end is marked, and
    # nothing else
    assert all(r.cancelled == (r.done > t0 + 3.0 or len(r.tokens) < 6
                               and r.tokens[-1:] != [0]) for r in recs)
    assert any(r.cancelled for r in recs)
    # the child: another process, pinned to the CPU, no TPU backend made,
    # its CPU time reported
    assert gen.stats["pid"] != os.getpid()
    assert gen.stats["jax_platforms"] == "cpu"
    assert "tpu" not in gen.stats["jax_backends"]
    assert gen.stats["cpu_user_s"] + gen.stats["cpu_sys_s"] > 0.0
    assert gen.stats["threads_left"] == 1
    # its ticker woke about four times a second, and says when it did not
    assert 0.25 <= gen.stats["longest_gap_s"] < 1.0
    assert 0.0 < gen.stats["longest_gap_at_s"] <= after - t0


def test_open_loop_runs_in_the_child_on_the_due_times(served):
    gen, srv, name = served
    requests = tiny_requests(6)
    dues = [0.0, 0.1, 0.2, 0.5, 0.6, 0.9]
    t0, recs = loadgen.run_open_loop(gen, name, requests, dues, 30.0)
    assert len(recs) == 6 and all(whole(r, requests) for r in recs)
    assert [round(r.due - t0, 6) for r in recs] == dues
    assert all(r.sent >= r.due for r in recs)
    assert all(r.ok(0, 64) and not r.cancelled for r in recs)


def test_a_failing_stream_counts_as_failed_not_as_missing(served):
    gen, srv, name = served
    requests = tiny_requests(3)
    _, recs = loadgen.run_open_loop(gen, "no_such_model", requests,
                                    [0.0] * 3, 30.0)
    assert len(recs) == 3
    assert all(r.error and r.done is not None and not r.ok(0, 64)
               for r in recs)
    # and the child lives on: the next job is served
    _, recs = loadgen.run_open_loop(gen, name, requests, [0.0] * 3, 30.0)
    assert all(r.ok(0, 64) for r in recs)


def test_the_generator_is_pinned_to_the_cpu_whatever_the_parent_says(
        served, monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    gen = loadgen.Generator()
    try:
        assert gen.hello()["jax_platforms"] == "cpu"
        assert gen.hello()["hello"] == gen._proc.pid != os.getpid()
        with pytest.raises(RuntimeError, match="serve"):
            gen.run("open", "lm", [], [], 1.0)
        # the child's death inside a window is an error, never an empty
        # window: killed one second into a closed loop
        _, srv, name = served
        gen.serve(srv.endpoint)
        threading.Timer(1.0, gen._proc.kill).start()
        with pytest.raises(loadgen.GeneratorDied, match="exit code -9"):
            loadgen.run_closed_loop(gen, name, tiny_requests(8), 2, 30.0)
    finally:
        gen.close()
    # a child that fails a job says why, and the parent raises it
    gen2, _, _ = served
    with pytest.raises(loadgen.GeneratorDied, match="KeyError"):
        gen2.run("no_such_loop", "lm", [])


def test_a_killed_run_leaves_no_generator_behind():
    """The driver ends a run at its time limit with SIGKILL; the child has
    to go with it, whatever loop it is in."""
    code = ("import sys, time; sys.path.insert(0, %r); "
            "from benchmark import loadgen; g = loadgen.Generator(); "
            "print(g.hello()['hello'], flush=True); time.sleep(120)" % ROOT)
    run = subprocess.Popen([sys.executable, "-c", code],
                           stdout=subprocess.PIPE, text=True)
    try:
        child = int(run.stdout.readline())
        assert os.path.exists("/proc/%d" % child)
        run.kill()
        run.wait(timeout=30)
        limit = time.monotonic() + 10.0
        while os.path.exists("/proc/%d" % child) and time.monotonic() < limit:
            time.sleep(0.1)
        assert not os.path.exists("/proc/%d" % child)
    finally:
        run.kill()
        run.stdout.close()


# ---------------------------------------------------------------------------
# whole cells at tiny size, off the chip (slow: tens of seconds each)
# ---------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.parametrize("cell,trace", [
    ("resnet50_feed_b256", 0), ("resnet50_feed_b256", 1),
    ("resnet50_dp4_loop_b1024", 1), ("gpt2s_decode_saturated", 0),
    ("gpt2s_decode_saturated", 1),
    # an open-loop cell added by a traffic file and manifest entries alone
    ("gpt2s_open_tiny", 0)])
def test_cell_rehearsal(manifest, cell, trace):
    from benchmark.tests.rehearse import (OPEN_TINY_MIX, add_open_cell,
                                          rehearse)
    patch = extra = None
    if cell == "gpt2s_open_tiny":
        patch, extra = add_open_cell, {"open_tiny": OPEN_TINY_MIX}
        manifest = json.loads(json.dumps(manifest))
        add_open_cell(manifest)
    rc, last, lines = rehearse(cell, trace, seconds=5.0, patch=patch,
                               extra_traffic=extra)
    assert rc == 0, lines[-5:]
    assert set(last) >= {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0 and last["device"]["platform"] == "cpu"
    want = manifest["per_layer"] if trace else manifest["end_to_end"]
    names = {m["name"] for m in want
             if "workloads" not in m or cell in m["workloads"]}
    if trace:
        names.discard("decode_attention_roofline")   # no Mosaic call on CPU
        assert last["device"]["busy_s"] > 0
        assert len(last["breakdown"]["device_ops"]) <= 10
    assert set(last["metrics"]) == names
    assert last["device"]["memory_peak_bytes"] >= 0
