"""Observability tests (paddle_tpu/obs — OBSERVABILITY.md).

Pins the tracing + telemetry contracts: the span ring never blocks or
grows, a served request's reply-visible trace_id resolves to a span
tree whose stages tile the root and land within 10% of the measured
client latency, the structured event log rotates atomically and
records the lifecycle events (hot swaps, sheds, sentinel actions,
checkpoint commits), the MetricsRegistry renders one Prometheus-style
surface across serving + training, and the CLIs (metrics_dump,
trace_top, serving_top --json) keep their schemas.  Everything
CPU-safe under JAX_PLATFORMS=cpu.
"""

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
import paddle_tpu.obs as obs
from paddle_tpu.flags import FLAGS, set_flags
from paddle_tpu.obs import events as obs_events
from paddle_tpu.obs import tracing as obs_tracing
from paddle_tpu.serving import (InferenceServer, ServerOverloaded,
                                ServingClient, ServingMetrics,
                                set_dispatch_delay)
from paddle_tpu.serving.metrics import ReservoirHistogram

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, os.path.join(REPO, "tools"))

import trace_top  # noqa: E402  (tools/trace_top.py)


@pytest.fixture(autouse=True)
def _obs_reset():
    """Every test starts with a fresh ring, default flags, and a
    memory-only event sink; chaos hooks cleared."""
    set_flags({"trace": True, "trace_buffer_events": 4096,
               "trace_slow_ms": 0.0, "event_log": "",
               "event_log_max_kb": 1024})
    obs_tracing.configure()
    obs_tracing.clear()
    obs_events.configure()
    yield
    set_dispatch_delay(0.0)
    set_flags({"trace": True, "trace_buffer_events": 4096,
               "trace_slow_ms": 0.0, "event_log": "",
               "event_log_max_kb": 1024})
    obs_tracing.configure()
    obs_tracing.clear()
    obs_events.configure()


def _export_fc(tmp_path, seed=3, name="m", size=6):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = seed
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[4], dtype="float32")
        h = fluid.layers.fc(input=x, size=size, act="relu")
        pred = fluid.layers.fc(input=h, size=size, act="softmax")
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        md = str(tmp_path / name)
        fluid.save_inference_model(md, ["x"], [pred], exe,
                                   main_program=main)
    return md


# ---------------------------------------------------------------------------
# tracing core
# ---------------------------------------------------------------------------

class TestTracing:
    def test_ring_wraps_at_capacity(self):
        set_flags({"trace_buffer_events": 16})
        for i in range(50):
            with obs.trace("t", i=i):
                pass
        st = obs_tracing.stats()
        assert st["buffered"] == 16
        assert st["spans_total"] == 50
        assert st["dropped"] == 34
        # the ring keeps the most recent spans
        kept = [s["attrs"]["i"] for s in obs.recent_spans()]
        assert kept == list(range(34, 50))

    def test_disabled_tracing_is_noop(self):
        set_flags({"trace": False})
        before = obs_tracing.stats()["spans_total"]
        with obs.trace("t") as s:
            assert s is None
        assert obs_tracing.stats()["spans_total"] == before
        set_flags({"trace": True})
        with obs.trace("t") as s:
            assert s is not None
        assert obs_tracing.stats()["spans_total"] == before + 1

    def test_exception_records_span_with_error_and_propagates(self):
        with pytest.raises(ValueError):
            with obs.trace("boom", kind="train"):
                raise ValueError("x")
        (span,) = obs.recent_spans(name="boom")
        assert span["attrs"]["error"] == "ValueError"

    def test_trace_ids_unique_hex(self):
        ids = {obs.new_trace_id() for _ in range(256)}
        assert len(ids) == 256
        assert all(len(t) == 16 and int(t, 16) >= 0 for t in ids)

    def test_spans_for_trace_filters(self):
        with obs.trace("a", trace_id="t1"):
            pass
        with obs.trace("b", trace_id="t2"):
            pass
        assert [s["name"] for s in obs.spans_for_trace("t1")] == ["a"]

    def test_concurrent_emitters_never_lose_the_ring(self):
        """Hot-path safety: hammering from threads neither raises nor
        corrupts the ring bookkeeping."""
        set_flags({"trace_buffer_events": 32})
        errs = []

        def hammer(k):
            try:
                for i in range(300):
                    with obs.trace("h%d" % k, i=i):
                        pass
            except BaseException as e:  # must never happen
                errs.append(e)

        ts = [threading.Thread(target=hammer, args=(k,))
              for k in range(4)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(10)
        assert not errs
        st = obs_tracing.stats()
        assert st["spans_total"] == 1200
        assert st["buffered"] == 32

    def test_chrome_events_merge_format(self):
        with obs.trace("serving/x", kind="serving", trace_id="tid1"):
            pass
        evs = obs_tracing.chrome_events()
        xs = [e for e in evs if e.get("ph") == "X"]
        assert xs and all(isinstance(e["tid"], int) for e in xs)
        assert any(e["args"].get("trace_id") == "tid1" for e in xs)
        assert any(e.get("ph") == "M" and e["name"] == "thread_name"
                   for e in evs)


# ---------------------------------------------------------------------------
# event log
# ---------------------------------------------------------------------------

class TestEventLog:
    def test_emit_schema_and_file_sink(self, tmp_path):
        path = str(tmp_path / "ev.jsonl")
        set_flags({"event_log": path})
        obs.emit("hot_swap", model="m", version=2, trace_id="abc")
        obs_events.get_log().flush()
        (rec,) = [json.loads(l) for l in open(path)]
        assert rec["kind"] == "hot_swap" and rec["model"] == "m"
        assert rec["version"] == 2 and rec["trace_id"] == "abc"
        assert isinstance(rec["ts"], float)
        assert obs.recent_events(kind="hot_swap")[-1]["version"] == 2

    def test_rotation_keeps_every_generation_valid(self, tmp_path):
        path = str(tmp_path / "ev.jsonl")
        set_flags({"event_log_max_kb": 1, "event_log": path})
        for i in range(200):   # ~60 bytes/line -> several rotations
            obs.emit("k", i=i)
        obs_events.get_log().flush()
        assert os.path.exists(path + ".1")
        seen = []
        for p in (path + ".1", path):   # rotated generation is older
            if os.path.exists(p):
                for line in open(p):
                    seen.append(json.loads(line)["i"])
        assert seen == sorted(seen)   # append-only, no tearing

    def test_sink_failure_is_memory_only_never_raises(self, tmp_path):
        # a path that cannot be opened: points INTO a regular file
        blocker = tmp_path / "f"
        blocker.write_text("x")
        set_flags({"event_log": str(blocker / "nope.jsonl")})
        with pytest.warns(UserWarning, match="memory-only"):
            obs.emit("k", i=1)
        obs.emit("k", i=2)   # sink dead: no second warning, no raise
        assert [e["i"] for e in obs.recent_events(kind="k")] == [1, 2]


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

class TestRegistry:
    def test_counters_gauges_histograms_render(self):
        reg = obs.MetricsRegistry()
        reg.counter("train_steps_total").add(3)
        reg.gauge("inflight", lambda: 2)
        h = reg.histogram("step_ms")
        for v in (1.0, 2.0, 3.0):
            h.record(v)
        txt = reg.prometheus_text()
        assert "paddle_tpu_train_steps_total 3" in txt
        assert "paddle_tpu_inflight 2" in txt
        assert 'paddle_tpu_step_ms{quantile="p50"} 2.0' in txt
        assert "paddle_tpu_step_ms_count 3" in txt

    def test_absorbs_serving_metrics(self):
        reg = obs.MetricsRegistry()
        sm = ServingMetrics()
        m = sm.model("zoo")
        m.requests.add(5)
        m.note_completion(latency_ms=10.0, queue_wait_ms=1.0)
        m.note_shed(priority=2)
        reg.attach_serving(sm)
        txt = reg.prometheus_text()
        assert 'paddle_tpu_serving_requests_total{model="zoo"} 5' in txt
        assert 'paddle_tpu_serving_latency_ms{model="zoo",' \
               'quantile="p50"} 10.0' in txt
        assert 'paddle_tpu_serving_shed_by_priority_total' \
               '{model="zoo",priority="2"} 1' in txt
        reg.detach_serving(sm)
        assert "zoo" not in reg.prometheus_text()

    def test_span_listener_aggregates_train_breakdown(self):
        reg = obs.default_registry()
        before = reg.span_totals().get(("train", "train/dispatch"),
                                       {"count": 0})["count"]
        with obs.trace("train/dispatch", kind="train", step=1):
            pass
        with obs.trace("train/dispatch", kind="train", step=2):
            pass
        agg = reg.span_totals(kind="train")[("train", "train/dispatch")]
        assert agg["count"] == before + 2
        assert agg["total_ms"] >= 0.0
        assert 'paddle_tpu_span_count_total{kind="train",' \
               'span="train/dispatch"}' in reg.prometheus_text()


class TestReservoirHistogramEdges:
    def test_empty_percentile_and_summary(self):
        h = ReservoirHistogram()
        assert h.percentile(50) is None
        assert h.summary() == {"count": 0}

    def test_capacity_one_keeps_a_valid_sample(self):
        h = ReservoirHistogram(capacity=1, seed=7)
        for v in range(100):
            h.record(float(v))
        assert h.count == 100
        s = h.summary()
        assert s["min"] == 0.0 and s["max"] == 99.0
        assert s["mean"] == pytest.approx(49.5)
        # the single reservoir slot holds SOME observed value, and every
        # percentile collapses to it
        assert 0.0 <= s["p50"] <= 99.0
        assert s["p50"] == s["p99"] == h.percentile(0)

    def test_single_value_every_percentile(self):
        h = ReservoirHistogram()
        h.record(42.0)
        for q in (0, 50, 99, 100):
            assert h.percentile(q) == 42.0
        s = h.summary()
        assert s["count"] == 1 and s["p95"] == 42.0


# ---------------------------------------------------------------------------
# serving end to end
# ---------------------------------------------------------------------------

@pytest.fixture()
def fc_server(tmp_path):
    md = _export_fc(tmp_path)
    srv = InferenceServer(endpoint="127.0.0.1:0").start()
    srv.registry.load_model("m", md, buckets=[2, 4, 8])
    cli = ServingClient(srv.endpoint)
    try:
        yield srv, cli, md
    finally:
        cli.close()
        srv.shutdown(drain=False, timeout=5.0)


class TestServingTracing:
    def test_trace_id_resolves_to_stage_tree_within_client_latency(
            self, fc_server):
        """THE acceptance criterion: the reply-visible trace_id
        resolves (trace RPC / ring) to a span tree whose stage
        durations tile the root exactly and land within 10% of the
        measured client latency; replies stay bit-exact vs a direct
        predictor run."""
        srv, cli, md = fc_server
        x = np.random.RandomState(0).randn(1, 4).astype(np.float32)
        cli.infer("m", {"x": x}, deadline_ms=10000)  # warm the wire
        set_dispatch_delay(0.15)   # compute dominates: 10% ≫ overhead
        t0 = time.monotonic()
        fetches, info = cli.infer("m", {"x": x}, deadline_ms=10000,
                                  debug=True)
        client_ms = (time.monotonic() - t0) * 1e3
        set_dispatch_delay(0.0)
        from paddle_tpu.inference import AnalysisConfig, Predictor
        cfg = AnalysisConfig(model_dir=md)
        cfg.batch_size_buckets = (2, 4, 8)
        ref = Predictor(cfg).run({"x": x})[0]
        assert np.array_equal(fetches[0], ref), "tracing changed bits"

        spans = cli.trace(trace_id=info["trace_id"])["spans"]
        stages = {s["name"]: s["dur_ms"] for s in spans}
        root = stages["serving/request"]
        stage_sum = sum(v for k, v in stages.items()
                        if k not in ("serving/request", "serving/rpc"))
        assert stage_sum == pytest.approx(root, rel=1e-6), \
            "stages must tile the root span"
        assert abs(stage_sum - client_ms) <= 0.10 * client_ms, \
            "span tree (%.1fms) vs client latency (%.1fms)" \
            % (stage_sum, client_ms)
        # the dominant stage is the injected dispatch stall
        assert stages["serving/dispatch"] >= 140.0

    def test_carried_wire_trace_id_is_echoed_and_used(self, fc_server):
        srv, cli, md = fc_server
        x = np.zeros((1, 4), np.float32)
        mine = "feedfacefeedface"
        fetches, info = cli.infer("m", {"x": x}, deadline_ms=10000,
                                  debug=True, trace_id=mine)
        assert info["trace_id"] == mine
        assert cli.last_trace_id == mine
        names = {s["name"] for s in cli.trace(trace_id=mine)["spans"]}
        assert "serving/request" in names and "serving/compute" in names

    def test_debug_reply_fields_and_plain_reply_shape(self, fc_server):
        srv, cli, md = fc_server
        x = np.zeros((2, 4), np.float32)
        fetches, info = cli.infer("m", {"x": x}, deadline_ms=10000,
                                  debug=True)
        for key in ("trace_id", "queue_wait_ms", "compute_ms",
                    "batch_fill", "batch_rows", "replica",
                    "server_ms"):
            assert key in info, key
        assert info["batch_rows"] >= 2
        # plain infer: list return unchanged, trace_id on the client
        out = cli.infer("m", {"x": x}, deadline_ms=10000)
        assert isinstance(out, list) and out[0].shape[0] == 2
        assert cli.last_trace_id

    def test_trace_off_still_serves_and_echoes_ids(self, fc_server):
        srv, cli, md = fc_server
        set_flags({"trace": False})
        before = obs_tracing.stats()["spans_total"]
        x = np.zeros((1, 4), np.float32)
        fetches, info = cli.infer("m", {"x": x}, deadline_ms=10000,
                                  debug=True)
        assert info["trace_id"]            # correlation id survives
        assert cli.trace(trace_id=info["trace_id"])["spans"] == []
        assert obs_tracing.stats()["spans_total"] == before

    def test_metrics_rpc_one_surface(self, fc_server):
        srv, cli, md = fc_server
        cli.infer("m", {"x": np.zeros((1, 4), np.float32)},
                  deadline_ms=10000)
        txt = cli.metrics_text()
        assert 'paddle_tpu_serving_requests_total{model="m"}' in txt
        assert "paddle_tpu_trace_spans_total" in txt
        assert "paddle_tpu_events_total" in txt
        assert 'span="serving/compute"' in txt

    def test_trace_rpc_kind_filter_and_limit(self, fc_server):
        srv, cli, md = fc_server
        for _ in range(3):
            cli.infer("m", {"x": np.zeros((1, 4), np.float32)},
                      deadline_ms=10000)
        spans = cli.trace(kind="serving", limit=5)["spans"]
        assert len(spans) == 5
        assert all(s["kind"] == "serving" for s in spans)

    def test_hot_swap_and_shed_events(self, tmp_path):
        md = _export_fc(tmp_path)
        srv = InferenceServer(endpoint="127.0.0.1:0",
                              max_queue=1).start()
        cli = ServingClient(srv.endpoint)
        try:
            srv.registry.load_model("m", md, buckets=[2, 4])
            srv.registry.load_model("m", md, buckets=[2, 4])  # hot swap
            swaps = obs.recent_events(kind="hot_swap")
            assert len(swaps) >= 2
            assert swaps[-1]["model"] == "m"
            assert swaps[-1]["from_version"] == 1
            assert swaps[-1]["version"] == 2
            ccs = obs.recent_events(kind="compile_cache_delta")
            assert ccs and ccs[-1]["model"] == "m"
            # overload a 1-deep queue with a concurrent burst: at least
            # one shed event with the priority class recorded
            set_dispatch_delay(0.2)
            x = np.zeros((1, 4), np.float32)
            sheds = []

            def one():
                c = ServingClient(srv.endpoint)
                try:
                    c.infer("m", {"x": x}, priority=1)
                except ServerOverloaded:
                    sheds.append(1)
                finally:
                    c.close()

            ts = [threading.Thread(target=one) for _ in range(12)]
            for t in ts:
                t.start()
            for t in ts:
                t.join(30)
            assert len(sheds) >= 1
            evs = obs.recent_events(kind="shed")
            assert evs and evs[-1]["priority"] == 1
            assert "trace_id" in evs[-1]
        finally:
            set_dispatch_delay(0.0)
            cli.close()
            srv.shutdown(drain=False, timeout=5.0)

    def test_slow_request_log_gated_by_flag(self, fc_server):
        srv, cli, md = fc_server
        set_flags({"trace_slow_ms": 50.0})
        x = np.zeros((1, 4), np.float32)
        cli.infer("m", {"x": x}, deadline_ms=10000)   # fast: no event
        assert not obs.recent_events(kind="slow")
        set_dispatch_delay(0.12)
        fetches, info = cli.infer("m", {"x": x}, deadline_ms=10000,
                                  debug=True)
        set_dispatch_delay(0.0)
        (ev,) = obs.recent_events(kind="slow")
        assert ev["trace_id"] == info["trace_id"]
        assert ev["total_ms"] >= 50.0


# ---------------------------------------------------------------------------
# training spans + events
# ---------------------------------------------------------------------------

def _regression_net():
    def train_func():
        x = fluid.layers.data("x", shape=[4], dtype="float32")
        y = fluid.layers.data("y", shape=[1], dtype="float32")
        pred = fluid.layers.fc(x, size=1)
        return fluid.layers.mean(
            fluid.layers.square_error_cost(pred, y))

    def optimizer_func():
        return fluid.optimizer.SGD(learning_rate=0.05)

    return train_func, optimizer_func


def _train(data, depth=0, prefetch=0, ckpt_dir=None, num_epochs=1,
           step_interval=4, sentinel=False):
    train_func, optimizer_func = _regression_net()

    def reader():
        for x, y in data:
            yield [(x, y)]

    flags = {"async_dispatch_depth": depth,
             "reader_prefetch_depth": prefetch,
             "sentinel_nan_check": sentinel}
    fluid.set_flags(flags)
    try:
        with fluid.scope_guard(fluid.Scope()):
            cfg = None
            if ckpt_dir is not None:
                cfg = fluid.contrib.CheckpointConfig(
                    checkpoint_dir=ckpt_dir,
                    step_interval=step_interval)
            trainer = fluid.contrib.Trainer(
                train_func, optimizer_func, place=fluid.CPUPlace(),
                checkpoint_config=cfg)
            losses = []

            def handler(ev):
                if isinstance(ev, fluid.contrib.EndStepEvent):
                    losses.append(np.asarray(ev.metrics[0]).copy())

            trainer.train(num_epochs=num_epochs, event_handler=handler,
                          reader=reader, feed_order=["x", "y"])
            return losses
    finally:
        fluid.set_flags({"async_dispatch_depth": 0,
                         "reader_prefetch_depth": 0,
                         "sentinel_nan_check": False})


def _regression_data(n=8, seed=0, poison_at=None):
    rng = np.random.RandomState(seed)
    out = []
    for i in range(n):
        x = rng.randn(4).astype(np.float32)
        y = np.array([x.sum()], np.float32)
        if poison_at is not None and i == poison_at:
            y[:] = np.nan
        out.append((x, y))
    return out


class TestTrainingSpans:
    def test_sync_loop_emits_per_step_spans(self):
        _train(_regression_data(6), depth=0)
        spans = obs.recent_spans(kind="train", name="train/step")
        assert len(spans) == 6
        assert [s["attrs"]["step"] for s in spans] == list(range(6))

    def test_async_loop_emits_dispatch_drain_ckpt_breakdown(
            self, tmp_path):
        _train(_regression_data(8), depth=3,
               ckpt_dir=str(tmp_path / "ckpt"), step_interval=4)
        names = [s["name"] for s in obs.recent_spans(kind="train")]
        assert names.count("train/dispatch") == 8
        assert names.count("train/drain") == 8
        assert "train/ckpt" in names
        # trace_top's per-step aggregation: each step shows dispatch
        # AND drain milliseconds (the per-step breakdown of the issue)
        steps = trace_top.group_steps(obs.recent_spans(kind="train"))
        by_step = {r["step"]: r for r in steps}
        # every dispatched step shows dispatch AND drain milliseconds
        # (ckpt spans carry GLOBAL step ids, so they may land in their
        # own rows — the breakdown still attributes them)
        for i in range(8):
            assert {"dispatch", "drain"} <= set(by_step[i]["stages"])
        assert any("ckpt" in r["stages"] for r in steps)

    def test_prefetch_wait_spans_recorded(self):
        _train(_regression_data(6), depth=0, prefetch=2)
        waits = obs.recent_spans(kind="train",
                                 name="train/prefetch_wait")
        assert len(waits) == 6

    def test_checkpoint_commit_event_stamped_with_step(self, tmp_path):
        _train(_regression_data(8), depth=0,
               ckpt_dir=str(tmp_path / "ckpt"), step_interval=4)
        evs = obs.recent_events(kind="checkpoint_committed")
        assert evs and evs[-1]["step"] >= 4
        assert "path" in evs[-1]

    def test_sentinel_skip_event_stamped_with_step(self):
        _train(_regression_data(8, poison_at=3), sentinel=True)
        evs = obs.recent_events(kind="sentinel_skip")
        assert evs and evs[-1]["step"] == 3
        assert "y" in evs[-1]["bad"] or evs[-1]["bad"]

    def test_drain_span_from_raw_fetchfuture(self):
        """fluid/pipeline.py instrumentation holds without the Trainer:
        any FetchFuture.result lands a train/drain span."""
        from paddle_tpu.fluid.pipeline import FetchFuture
        fut = FetchFuture([np.float32(1.0)])
        fut.result(step=7)
        (s,) = obs.recent_spans(kind="train", name="train/drain")
        assert s["attrs"]["step"] == 7


# ---------------------------------------------------------------------------
# profiler merge
# ---------------------------------------------------------------------------

# ---------------------------------------------------------------------------
# phase spans where the chip waits: Executor.run, DecodeSession, the lane
# ---------------------------------------------------------------------------

def _fc_regression():
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 5
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[4], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="float32")
        pred = fluid.layers.fc(input=x, size=1)
        loss = fluid.layers.mean(fluid.layers.square_error_cost(
            input=pred, label=y))
        fluid.optimizer.SGD(learning_rate=0.01).minimize(loss)
    return main, startup, loss


def _run_steps(feeds):
    """Loss of each step of a fresh fc regression over `feeds`."""
    main, startup, loss = _fc_regression()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        obs_tracing.clear()
        return [exe.run(main, feed=f, fetch_list=[loss])[0] for f in feeds]


def _tree(spans, root):
    """{child name: span} of the children stamped with `root`'s t0..end."""
    return {s["name"]: s for s in spans if s.get("parent") == root["name"]
            and root["t0"] <= s["t0"] <= root["t0"] + root["dur_ms"] * 1e-3}


class _NoClock(object):
    """Stands in for the `time` module of an instrumented file: with
    tracing off no instrumented call may read the clock."""

    def __init__(self):
        self.sleep = time.sleep

    def monotonic(self):
        raise AssertionError("time.monotonic() read with FLAGS.trace off")


@pytest.fixture(scope="module")
def tiny_decode_dir(tmp_path_factory):
    from paddle_tpu.inference.decode import build_tiny_decode_model
    d = str(tmp_path_factory.mktemp("obs_decode") / "lm")
    build_tiny_decode_model(d)
    return d


class TestPhaseSpans:
    def test_executor_run_children_tile_it_and_count_bytes(self):
        import jax.numpy as jnp
        rng = np.random.RandomState(0)
        x = rng.randn(8, 4).astype(np.float32)
        y = rng.randn(8, 1).astype(np.float32)
        _run_steps([{"x": x, "y": y}, {"x": x, "y": y},
                    {"x": jnp.asarray(x), "y": jnp.asarray(y)}])
        spans = obs.recent_spans(kind="train")
        roots = [s for s in spans if s["name"] == "executor/run"]
        assert len(roots) == 3
        assert [r["attrs"]["path"] for r in roots] == ["jit"] * 3
        assert [r["attrs"]["steps"] for r in roots] == [1, 1, 1]
        want_h2d = [x.nbytes + y.nbytes, x.nbytes + y.nbytes, 0]
        for root, h2d, compiled in zip(roots, want_h2d, [1, 0, 0]):
            kids = _tree(spans, root)
            assert set(kids) == {"executor/feed", "executor/dispatch",
                                 "executor/fetch"}
            assert abs(sum(k["dur_ms"] for k in kids.values())
                       - root["dur_ms"]) < 1.0
            assert kids["executor/feed"]["t0"] == root["t0"]
            assert (kids["executor/feed"]["t0"]
                    <= kids["executor/dispatch"]["t0"]
                    <= kids["executor/fetch"]["t0"])
            assert kids["executor/feed"]["attrs"]["h2d_bytes"] == h2d
            assert kids["executor/feed"]["attrs"]["cast_bytes"] == 0
            disp = kids["executor/dispatch"]["attrs"]
            assert disp["compiled"] == compiled
            assert disp["state_host_bytes"] == 0
            assert kids["executor/fetch"]["attrs"]["d2h_bytes"] == 4
            assert {k["attrs"]["step"] for k in kids.values()} == \
                {root["attrs"]["step"]}

    def test_executor_feed_counts_host_casts(self):
        x = np.zeros((8, 4), np.float64)
        _run_steps([{"x": x, "y": np.zeros((8, 1), np.float32)}])
        (feed,) = obs.recent_spans(name="executor/feed")
        assert feed["attrs"]["h2d_bytes"] == 8 * 4 * 4 + 8 * 4
        assert feed["attrs"]["cast_bytes"] == 8 * 4 * 4

    def test_executor_spans_take_the_step_of_the_span_around_them(self):
        main, startup, loss = _fc_regression()
        exe = fluid.Executor(fluid.CPUPlace())
        feed = {"x": np.zeros((2, 4), np.float32),
                "y": np.zeros((2, 1), np.float32)}
        with fluid.scope_guard(fluid.Scope()):
            exe.run(startup)
            with obs.trace("train/step", kind="train", step=41):
                exe.run(main, feed=feed, fetch_list=[loss])
        (root,) = obs.recent_spans(name="executor/run")[-1:]
        assert root["parent"] == "train/step"
        assert root["attrs"]["step"] == 41

    def test_run_loop_emits_one_run_span_for_its_steps(self):
        main, startup, loss = _fc_regression()
        exe = fluid.Executor(fluid.CPUPlace())
        feed = {"x": np.ones((2, 4), np.float32),
                "y": np.ones((2, 1), np.float32)}
        with fluid.scope_guard(fluid.Scope()):
            exe.run(startup)
            obs_tracing.clear()
            exe.run_loop(main, feed=feed, fetch_list=[loss], steps=3)
        (root,) = obs.recent_spans(name="executor/run")
        assert root["attrs"]["steps"] == 3
        assert set(_tree(obs.recent_spans(), root)) == {
            "executor/feed", "executor/dispatch", "executor/fetch"}

    @pytest.mark.parametrize("placed", [False, True],
                             ids=["default_placement", "on_a_device"])
    def test_decode_launch_counts_what_the_call_uploads(
            self, tiny_decode_dir, placed):
        import jax
        from paddle_tpu.inference.decode import GenerativePredictor
        pred = GenerativePredictor(
            tiny_decode_dir, device=jax.devices()[0] if placed else None)
        sess = pred.new_session(4)
        sess.prefill(0, [3, 1, 4])
        obs_tracing.clear()
        sess.decode()
        by = {s["name"]: s for s in obs.recent_spans(kind="serving")}
        assert set(by) == {"decode/put", "decode/launch", "decode/fetch"}
        assert {s["attrs"]["phase"] for s in by.values()} == {"step"}
        # lengths, last_tokens, active, budget, max_trips
        small = 4 * 4 + 4 * 4 + 4 + 4 * 4 + 4
        # all three carry the trips the dispatch ran
        assert {s["attrs"]["trips"] for s in by.values()} == {1}
        # the weights are resident under either placement: the launch
        # uploads no more than the small arguments `_put` left on the host
        # (none where it placed them on the device)
        assert pred.state_host_bytes() == 0
        assert by["decode/launch"]["attrs"]["h2d_bytes"] == \
            (0 if placed else small)
        # ONE int32 vector: the window's token block, 4 counts, the trips
        from paddle_tpu.inference.decode import STEP_WINDOW
        assert by["decode/fetch"]["attrs"]["d2h_bytes"] \
            == 4 * (4 * STEP_WINDOW + 4 + 1)
        assert (by["decode/put"]["t0"] <= by["decode/launch"]["t0"]
                <= by["decode/fetch"]["t0"])

    def test_every_span_of_a_decode_round_has_parent_and_round(
            self, tiny_decode_dir):
        from paddle_tpu.inference.decode import GenerativePredictor
        from paddle_tpu.serving.batcher import DecodeBatcher
        pred = GenerativePredictor(tiny_decode_dir)
        b = DecodeBatcher(pred, n_slots=2)
        try:
            with b._cv:         # both admitted by one pass of the lane
                streams = [b.submit([5, 9, 3], max_new_tokens=11,
                                    trace_id="round-a"),
                           b.submit([7, 2], max_new_tokens=4)]
            for s in streams:
                s.result(timeout=60)
        finally:
            b.close()
        spans = obs.recent_spans(kind="serving")
        iters = [s for s in spans if s["name"] == "serving/lane_iter"]
        steps = [s for s in spans if s["name"] == "serving/decode_step"]
        # both slots assigned: ONE full window, in which the shorter
        # stream stops at its third trip and sits five out, then the
        # other's last two
        assert iters and [(s["attrs"]["trips"], s["attrs"]["tokens"])
                          for s in steps] == [(8, 8 + 3), (2, 2)]
        assert sum(i["attrs"]["admits"] for i in iters) == 2
        assert sum(i["attrs"]["emitted"] for i in iters) == \
            sum(s["attrs"]["tokens"] for s in steps) == 11 + 4 - 2
        by_round = {i["attrs"]["round"]: i for i in iters
                    if i["attrs"]["emitted"] or not i["attrs"]["admits"]}
        for step in steps:
            rnd = step["attrs"]["round"]
            it = by_round[rnd]
            assert step["parent"] == "serving/lane_iter"
            end = it["t0"] + it["dur_ms"] * 1e-3
            mine = [s for s in spans
                    if s.get("attrs", {}).get("round") == rnd
                    and s["name"] != "serving/lane_iter"
                    and it["t0"] <= s["t0"] <= end]
            # each of the two dispatches ends a stream: its delivery holds
            # one finish, and that the slot's release
            assert {s["name"] for s in mine} == {
                "serving/decode_step", "serving/emit", "serving/finish",
                "serving/slot_free", "decode/put", "decode/launch",
                "decode/fetch"}
            for s in mine:
                want = "serving/decode_step" \
                    if s["name"].startswith("decode/") \
                    else {"serving/finish": "serving/emit",
                          "serving/slot_free": "serving/finish"}.get(
                              s["name"], "serving/lane_iter")
                assert s["parent"] == want, s
                # t0 never runs backwards down the tree
                assert it["t0"] <= s["t0"]
                if s["name"].startswith("decode/"):
                    assert step["t0"] <= s["t0"] <= \
                        step["t0"] + step["dur_ms"] * 1e-3 + 1e-6
        # a prefill's session spans hang under the request's trace id
        pre = [s for s in spans if s.get("trace_id") == "round-a"
               and s.get("attrs", {}).get("phase") == "prefill"]
        assert {s["name"] for s in pre} == {"decode/put", "decode/launch",
                                            "decode/fetch"}
        assert {s["parent"] for s in pre} == {"serving/prefill_compute"}
        (pc,) = [s for s in spans if s["name"] == "serving/prefill_compute"
                 and s.get("trace_id") == "round-a"]
        assert pc["parent"] == "serving/lane_iter"

    def test_trace_off_emits_nothing_reads_no_clock_same_results(
            self, tiny_decode_dir, monkeypatch):
        from paddle_tpu.fluid import executor as executor_mod
        from paddle_tpu.inference import decode as decode_mod
        rng = np.random.RandomState(2)
        feeds = [{"x": rng.randn(8, 4).astype(np.float32),
                  "y": rng.randn(8, 1).astype(np.float32)}
                 for _ in range(3)]

        def decode_tokens():
            pred = decode_mod.GenerativePredictor(tiny_decode_dir)
            sess = pred.new_session(2)
            out = [sess.prefill(0, [3, 1, 4, 1, 5])]
            out += [int(sess.decode()[0]) for _ in range(4)]
            out += [t.tolist() for t in sess.decode_fused(3)[:2]]
            return out

        on = (_run_steps(feeds), decode_tokens())
        assert obs_tracing.stats()["spans_total"] > 0
        set_flags({"trace": False})
        obs_tracing.clear()
        monkeypatch.setattr(executor_mod, "time", _NoClock())
        monkeypatch.setattr(decode_mod, "time", _NoClock())
        off = (_run_steps(feeds), decode_tokens())
        assert obs_tracing.stats()["spans_total"] == 0
        assert all(np.array_equal(a, b) for a, b in zip(on[0], off[0]))
        assert on[1] == off[1]

    def test_every_emitter_is_on_the_one_clock(self, tiny_decode_dir):
        from paddle_tpu.inference.decode import GenerativePredictor
        from paddle_tpu.serving.batcher import DecodeBatcher
        before = time.monotonic()
        with obs.trace("t"):
            pass
        _run_steps([{"x": np.zeros((2, 4), np.float32),
                     "y": np.zeros((2, 1), np.float32)}])
        with obs.trace("t"):
            pass
        b = DecodeBatcher(GenerativePredictor(tiny_decode_dir), n_slots=1)
        try:
            b.submit([5, 9], max_new_tokens=3).result(timeout=60)
        finally:
            b.close()
        spans = obs.recent_spans()
        names = {s["name"] for s in spans}
        assert {"t", "executor/run", "executor/feed", "serving/request",
                "serving/queue_wait", "serving/decode_step",
                "serving/prefill_compute", "decode/launch"} <= names
        offsets = [s["ts"] - s["t0"] for s in spans]
        # `ts` is `t0` plus ONE offset (float rounding at epoch size only)
        assert max(offsets) - min(offsets) < 1e-5
        assert abs(offsets[0] - (time.time() - time.monotonic())) < 5.0
        assert all(before <= s["t0"] <= time.monotonic() for s in spans)

    def test_trace_top_train_shows_the_executor_phases(self):
        main, startup, loss = _fc_regression()
        exe = fluid.Executor(fluid.CPUPlace())
        feed = {"x": np.zeros((2, 4), np.float32),
                "y": np.zeros((2, 1), np.float32)}
        with fluid.scope_guard(fluid.Scope()):
            exe.run(startup)
            obs_tracing.clear()
            with obs.trace("train/step", kind="train", step=7):
                exe.run(main, feed=feed, fetch_list=[loss])
            exe.run(main, feed=feed, fetch_list=[loss])   # a bare loop
        recs = {r["step"]: r for r in trace_top.group_steps(
            obs.recent_spans(kind="train"))}
        assert set(recs) == {7, 1}
        for r in recs.values():
            assert set(r["executor"]) == {"feed", "dispatch", "fetch"}
            assert sum(r["executor"].values()) <= r["total_ms"] + 1e-3
        assert set(recs[7]["stages"]) == {"step"}
        assert recs[1]["stages"] == {} and recs[1]["total_ms"] > 0
        table = trace_top.render_steps(list(recs.values()), 5)
        assert "executor feed|dispatch|fetch" in table.splitlines()[0]
        assert " | " in table.splitlines()[1]

    def test_under_declares_a_parent_without_emitting(self):
        with obs_tracing.under("outer", trace_id="tid", round=7):
            with obs.trace("inner"):
                t = time.monotonic()
                obs_tracing.stamp("stamped", t, t + 0.001, kind="x")
        spans = obs.recent_spans()
        assert [s["name"] for s in spans] == ["stamped", "inner"]
        assert spans[0]["parent"] == "inner"
        assert spans[1]["parent"] == "outer"
        for s in spans:
            assert s["trace_id"] == "tid" and s["attrs"]["round"] == 7
        with obs.trace("alone") as s:
            assert s.parent is None


class TestChromeMerge:
    def test_export_chrome_tracing_merges_obs_spans(self, tmp_path):
        import gzip
        d = tmp_path / "plugins" / "profile" / "run1"
        d.mkdir(parents=True)
        device = {"traceEvents": [
            {"ph": "X", "pid": 1, "tid": 1, "name": "xla::fusion",
             "ts": 0, "dur": 5}]}
        with gzip.open(str(d / "host.trace.json.gz"), "wb") as f:
            f.write(json.dumps(device).encode())
        with obs.trace("serving/compute", kind="serving",
                       trace_id="zz"):
            pass
        out = fluid.profiler.export_chrome_tracing(
            trace_dir=str(tmp_path),
            output_path=str(tmp_path / "merged.json"))
        data = json.load(open(out))
        names = {e.get("name") for e in data["traceEvents"]}
        assert "xla::fusion" in names          # device timeline kept
        assert "serving/compute" in names      # obs spans merged in


# ---------------------------------------------------------------------------
# CLIs + chaos (tier-1 smokes)
# ---------------------------------------------------------------------------

def _run_cli(args, timeout=120):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable] + args, capture_output=True, text=True,
        timeout=timeout, cwd=REPO, env=env)


# serving_top --json top-level + per-model keys dashboards depend on;
# additive evolution only — removing/renaming breaks consumers silently
SERVING_TOP_MODEL_KEYS = {
    "model", "uptime_sec", "requests", "responses", "errors", "shed",
    "deadline_expired", "dispatches", "qps_recent", "qps_lifetime",
    "batch_fill", "bucket_fill_ratio", "latency_ms", "queue_wait_ms",
    "compile_cache", "queue_depth", "replicas"}


class TestCLIs:
    def test_serving_top_json_schema_pinned(self, fc_server):
        srv, cli, md = fc_server
        cli.infer("m", {"x": np.zeros((1, 4), np.float32)},
                  deadline_ms=10000)
        proc = _run_cli(["tools/serving_top.py", srv.endpoint,
                         "--json"])
        assert proc.returncode == 0, proc.stderr
        reply = json.loads(proc.stdout)
        assert {"ok", "stats", "models"} <= set(reply)
        assert {"uptime_sec", "models", "tokens_sent_total"} \
            <= set(reply["stats"])
        m = reply["stats"]["models"]["m"]
        missing = SERVING_TOP_MODEL_KEYS - set(m)
        assert not missing, "snapshot keys went missing: %s" % missing
        assert {"count", "mean", "p50", "p95", "p99", "min", "max"} \
            <= set(m["latency_ms"])

    def test_metrics_dump_cli_smoke(self, fc_server):
        srv, cli, md = fc_server
        cli.infer("m", {"x": np.zeros((1, 4), np.float32)},
                  deadline_ms=10000)
        proc = _run_cli(["tools/metrics_dump.py", srv.endpoint])
        assert proc.returncode == 0, proc.stderr
        assert 'paddle_tpu_serving_requests_total{model="m"}' \
            in proc.stdout
        assert "# TYPE" in proc.stdout

    def test_trace_top_cli_smoke(self, fc_server):
        srv, cli, md = fc_server
        x = np.zeros((1, 4), np.float32)
        fetches, info = cli.infer("m", {"x": x}, deadline_ms=10000,
                                  debug=True)
        top = _run_cli(["tools/trace_top.py", srv.endpoint, "-n", "5"])
        assert top.returncode == 0, top.stderr
        assert info["trace_id"] in top.stdout
        assert "queue_wait=" in top.stdout
        tree = _run_cli(["tools/trace_top.py", srv.endpoint,
                         "--trace_id", info["trace_id"]])
        assert tree.returncode == 0, tree.stderr
        assert "serving/request" in tree.stdout
        js = _run_cli(["tools/trace_top.py", srv.endpoint, "--json"])
        recs = json.loads(js.stdout)
        assert recs and {"trace_id", "total_ms", "stages"} \
            <= set(recs[0])

    def test_chaos_trace_overflow_scenario(self, tmp_path):
        """The hot path never blocks or crashes under ring overflow +
        event-log rotation faults (satellite: chaos scenario)."""
        import chaos
        out = chaos.scenario_trace_overflow(str(tmp_path / "ov"),
                                            verbose=False)
        assert out["dropped"] > 0
        assert out["max_emit_ms"] < 250.0
