"""Serving runtime tests (paddle_tpu/serving — SERVING.md).

Pins the subsystem's contracts: cross-request coalescing with bit-exact
padding parity vs a direct Predictor.run, registry hot swap that never
drops or double-answers a request, admission-control shedding that
never hangs (including under FlakyProxy transport chaos), graceful
drain on shutdown, and wire-encodable metrics.  Everything CPU-safe
under JAX_PLATFORMS=cpu.
"""

import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.serving import (
    BatcherClosed, DeadlineExceeded, DynamicBatcher, InferenceServer,
    ModelRegistry, ServerOverloaded, ServingClient, ServingMetrics,
    set_dispatch_delay)

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)


@pytest.fixture(autouse=True)
def _clear_chaos():
    yield
    set_dispatch_delay(0.0)


def _export_fc(tmp_path, seed, name="m", size=6, with_aux=False):
    """Tiny fc model -> save_inference_model dir; returns its path."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = seed
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[4], dtype="float32")
        feeds = ["x"]
        h = fluid.layers.fc(input=x, size=size, act="relu")
        if with_aux:
            aux = fluid.layers.data(name="aux", shape=[size],
                                    dtype="float32",
                                    append_batch_size=False)
            h = fluid.layers.elementwise_add(h, aux, axis=-1)
            feeds.append("aux")
        pred = fluid.layers.fc(input=h, size=size, act="softmax")
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        md = str(tmp_path / name)
        fluid.save_inference_model(md, feeds, [pred], exe,
                                   main_program=main)
    return md


def _direct(md, buckets=(2, 4, 8)):
    from paddle_tpu.inference import AnalysisConfig, Predictor
    cfg = AnalysisConfig(model_dir=md)
    cfg.batch_size_buckets = tuple(buckets)
    return Predictor(cfg)


# ---------------------------------------------------------------------------
# batcher
# ---------------------------------------------------------------------------

class TestBatcher:
    def test_coalesces_and_matches_direct_run_bit_exact(self, tmp_path):
        md = _export_fc(tmp_path, seed=3)
        direct = _direct(md)
        pred = _direct(md)
        metrics = ServingMetrics().model("m")
        batcher = DynamicBatcher(pred, max_queue=64, deadline_ms=50,
                                 metrics=metrics)
        rng = np.random.RandomState(0)
        inputs = [rng.randn(b, 4).astype(np.float32)
                  for b in (1, 2, 3, 1, 1)]
        refs = [direct.run({"x": xi})[0] for xi in inputs]
        try:
            futures = [batcher.submit({"x": xi}) for xi in inputs]
            outs = [f.result(timeout=30)[0] for f in futures]
        finally:
            batcher.close()
        for xi, out, ref in zip(inputs, outs, refs):
            assert out.shape == ref.shape
            assert np.array_equal(out, ref), \
                "coalesced+padded result differs from direct run"
        # all 5 requests (total 8 rows) fit the largest bucket and were
        # queued before the window closed: strictly fewer dispatches
        assert metrics.dispatches.value < len(inputs)
        assert metrics.requests.value == len(inputs)
        assert metrics.responses.value == len(inputs)

    def test_side_feed_compatibility_grouping(self, tmp_path):
        """Requests sharing a byte-identical side feed coalesce; ones
        with a different side feed dispatch separately but correctly."""
        md = _export_fc(tmp_path, seed=4, with_aux=True)
        direct = _direct(md)
        pred = _direct(md)
        batcher = DynamicBatcher(pred, max_queue=64, deadline_ms=50)
        rng = np.random.RandomState(1)
        aux_a = rng.randn(6).astype(np.float32)
        aux_b = rng.randn(6).astype(np.float32)
        reqs = [(rng.randn(1, 4).astype(np.float32), aux)
                for aux in (aux_a, aux_a, aux_b, aux_a)]
        refs = [direct.run({"x": x, "aux": a})[0] for x, a in reqs]
        try:
            futs = [batcher.submit({"x": x, "aux": a}) for x, a in reqs]
            outs = [f.result(timeout=30)[0] for f in futs]
        finally:
            batcher.close()
        for out, ref in zip(outs, refs):
            assert np.array_equal(out, ref)

    def test_oversize_request_rejected_synchronously(self, tmp_path):
        md = _export_fc(tmp_path, seed=5)
        batcher = DynamicBatcher(_direct(md, buckets=(2, 4)),
                                 max_queue=8, deadline_ms=1)
        try:
            with pytest.raises(ValueError, match="largest servable"):
                batcher.submit({"x": np.zeros((9, 4), np.float32)})
        finally:
            batcher.close()

    def test_inconsistent_batch_rejected(self, tmp_path):
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = 5
        with fluid.program_guard(main, startup):
            a = fluid.layers.data(name="a", shape=[4], dtype="float32")
            b = fluid.layers.data(name="b", shape=[4], dtype="float32")
            out = fluid.layers.elementwise_add(a, b)
        exe = fluid.Executor(fluid.CPUPlace())
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe.run(startup)
            md = str(tmp_path / "two_feed")
            fluid.save_inference_model(md, ["a", "b"], [out], exe,
                                       main_program=main)
        batcher = DynamicBatcher(_direct(md), max_queue=8, deadline_ms=1)
        try:
            with pytest.raises(ValueError, match="inconsistent"):
                batcher.submit({"a": np.zeros((2, 4), np.float32),
                                "b": np.zeros((3, 4), np.float32)})
        finally:
            batcher.close()

    def test_deadline_zero_dispatches_immediately(self, tmp_path):
        md = _export_fc(tmp_path, seed=6)
        batcher = DynamicBatcher(_direct(md), max_queue=8, deadline_ms=0)
        try:
            t0 = time.monotonic()
            out = batcher.submit(
                {"x": np.zeros((1, 4), np.float32)}).result(timeout=30)
            assert out[0].shape == (1, 6)
            assert time.monotonic() - t0 < 5.0
        finally:
            batcher.close()

    def test_overload_sheds_and_counts(self, tmp_path):
        md = _export_fc(tmp_path, seed=7)
        metrics = ServingMetrics().model("m")
        batcher = DynamicBatcher(_direct(md), max_queue=3, deadline_ms=5,
                                 metrics=metrics)
        set_dispatch_delay(0.2)
        x = np.zeros((1, 4), np.float32)
        accepted, shed = [], 0
        try:
            for _ in range(16):
                try:
                    accepted.append(batcher.submit({"x": x}))
                except ServerOverloaded:
                    shed += 1
            assert shed > 0
            assert metrics.shed.value == shed
            set_dispatch_delay(0.0)
            for f in accepted:  # accepted requests still complete
                f.result(timeout=30)
        finally:
            set_dispatch_delay(0.0)
            batcher.close()

    def test_request_deadline_expires_in_queue(self, tmp_path):
        md = _export_fc(tmp_path, seed=8)
        batcher = DynamicBatcher(_direct(md), max_queue=32, deadline_ms=1)
        set_dispatch_delay(0.3)
        x = np.zeros((1, 4), np.float32)
        try:
            batcher.submit({"x": x})  # occupies the slow worker
            fut = batcher.submit(
                {"x": x}, deadline=time.monotonic() + 0.05)
            with pytest.raises(DeadlineExceeded):
                fut.result(timeout=30)
        finally:
            set_dispatch_delay(0.0)
            batcher.close()

    def test_close_drains_queued_requests(self, tmp_path):
        md = _export_fc(tmp_path, seed=9)
        batcher = DynamicBatcher(_direct(md), max_queue=64, deadline_ms=2)
        set_dispatch_delay(0.05)
        x = np.zeros((2, 4), np.float32)
        futs = [batcher.submit({"x": x}) for _ in range(10)]
        set_dispatch_delay(0.0)
        batcher.close(drain=True, timeout=60)
        for f in futs:
            assert f.result(timeout=1)[0].shape == (2, 6)
        with pytest.raises(BatcherClosed):
            batcher.submit({"x": x})


# ---------------------------------------------------------------------------
# registry / hot swap
# ---------------------------------------------------------------------------

class TestRegistry:
    def test_aot_artifact_detection_and_serving(self, tmp_path):
        md = _export_fc(tmp_path, seed=10)
        direct = _direct(md)
        aot = str(tmp_path / "aot")
        direct.save_aot(aot, batch_sizes=(2, 4))
        reg = ModelRegistry(deadline_ms=5)
        try:
            entry = reg.load_model("m", aot)
            from paddle_tpu.inference import AotPredictor
            assert isinstance(entry.predictor, AotPredictor)
            assert entry.predictor.batch_buckets() == (2, 4)
            x = np.random.RandomState(2).randn(3, 4).astype(np.float32)
            out = reg.infer("m", {"x": x}, timeout=60)[0]
            ref = direct.run({"x": x})[0]
            np.testing.assert_allclose(out, ref, rtol=2e-5, atol=1e-6)
        finally:
            reg.close_all()

    def test_versioning_and_explicit_version_routing(self, tmp_path):
        md1 = _export_fc(tmp_path, seed=11, name="v1")
        md2 = _export_fc(tmp_path, seed=22, name="v2")
        reg = ModelRegistry(deadline_ms=1)
        try:
            e1 = reg.load_model("m", md1, buckets=(2, 4))
            e2 = reg.load_model("m", md2, buckets=(2, 4), version=7)
            assert (e1.version, e2.version) == (1, 7)
            x = np.random.RandomState(3).randn(1, 4).astype(np.float32)
            r1 = _direct(md1, (2, 4)).run({"x": x})[0]
            latest = reg.infer("m", {"x": x}, timeout=60)[0]
            assert not np.array_equal(latest, r1)
            # the displaced version is retired: explicit routing to it
            # now fails rather than silently serving stale weights
            with pytest.raises(KeyError):
                reg.submit("m", {"x": x}, version=1)
        finally:
            reg.close_all()

    def test_hot_swap_under_concurrent_inference(self, tmp_path):
        """The no-dropped-no-doubled guarantee: hammer one model name
        from 3 threads while hot-swapping versions; every response must
        be exactly v1's or v2's output, every submit must resolve."""
        md1 = _export_fc(tmp_path, seed=31, name="v1")
        md2 = _export_fc(tmp_path, seed=32, name="v2")
        x = np.random.RandomState(4).randn(2, 4).astype(np.float32)
        r1 = _direct(md1, (2, 4)).run({"x": x})[0]
        r2 = _direct(md2, (2, 4)).run({"x": x})[0]
        reg = ModelRegistry(deadline_ms=2)
        reg.load_model("m", md1, buckets=(2, 4))
        stop = threading.Event()
        wrong, errors, answered = [], [], [0]
        lock = threading.Lock()

        def hammer():
            while not stop.is_set():
                try:
                    out = reg.infer("m", {"x": x}, timeout=30)[0]
                except Exception as e:  # no exception is acceptable
                    errors.append(e)
                    return
                with lock:
                    answered[0] += 1
                    if not (np.array_equal(out, r1)
                            or np.array_equal(out, r2)):
                        wrong.append(out)

        threads = [threading.Thread(target=hammer) for _ in range(3)]
        try:
            for t in threads:
                t.start()
            time.sleep(0.2)
            reg.load_model("m", md2, buckets=(2, 4))  # hot swap mid-load
            time.sleep(0.2)
        finally:
            stop.set()
            for t in threads:
                t.join(timeout=30)
        assert not errors, errors[:3]
        assert not wrong, "%d responses matched neither version" \
            % len(wrong)
        assert answered[0] > 10
        out_after = reg.infer("m", {"x": x}, timeout=30)[0]
        assert np.array_equal(out_after, r2), \
            "post-swap traffic must serve the new version"
        reg.close_all()

    def test_unload_refuses_new_traffic(self, tmp_path):
        md = _export_fc(tmp_path, seed=12)
        reg = ModelRegistry(deadline_ms=1)
        reg.load_model("m", md, buckets=(2,))
        reg.unload_model("m")
        with pytest.raises(KeyError):
            reg.submit("m", {"x": np.zeros((1, 4), np.float32)})
        reg.close_all()


# ---------------------------------------------------------------------------
# server end-to-end
# ---------------------------------------------------------------------------

class TestServer:
    def test_e2e_concurrent_clients_bit_exact_and_coalesced(
            self, tmp_path):
        """The acceptance demo: in-process server on a saved model, 3+
        concurrent clients with mixed batch sizes, bit-exact vs direct
        Predictor.run, batch-fill > 1 request/dispatch."""
        md = _export_fc(tmp_path, seed=13)
        direct = _direct(md)
        server = InferenceServer(buckets=(2, 4, 8),
                                 deadline_ms=20).start()
        rng = np.random.RandomState(5)
        inputs = [rng.randn(b, 4).astype(np.float32)
                  for b in (1, 2, 3, 1, 2, 1)]
        refs = [direct.run({"x": xi})[0] for xi in inputs]
        outs = [None] * len(inputs)
        errs = []
        try:
            boot = ServingClient(server.endpoint)
            boot.load_model("fc", md, buckets=[2, 4, 8])

            def worker(i):
                cli = ServingClient(server.endpoint)
                try:
                    outs[i] = cli.infer("fc", {"x": inputs[i]},
                                        deadline_ms=30000.0)[0]
                except Exception as e:
                    errs.append(e)
                finally:
                    cli.close()

            threads = [threading.Thread(target=worker, args=(i,))
                       for i in range(len(inputs))]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
            assert not errs, errs[:3]
            for out, ref in zip(outs, refs):
                assert np.array_equal(out, ref), \
                    "served result differs from direct Predictor.run"
            stats = boot.stats()["stats"]["models"]["fc"]
            assert stats["responses"] == len(inputs)
            assert stats["batch_fill"] > 1.0, \
                "no cross-request coalescing happened: %r" % stats
            assert stats["latency_ms"]["count"] == len(inputs)
        finally:
            server.shutdown(drain=True)

    def test_overload_sheds_not_hangs_under_flaky_proxy(self, tmp_path):
        """Chaos acceptance: tiny admission queue + slow worker + a
        connection-killing proxy; every request resolves (ok / shed /
        deadline / connection error), none hang."""
        sys.path.insert(0, os.path.join(REPO, "tools"))
        from chaos import FlakyProxy
        md = _export_fc(tmp_path, seed=14)
        server = InferenceServer(max_queue=3, buckets=(2, 4)).start()
        proxy = FlakyProxy(server.endpoint, drop_first=2,
                           drop_after_bytes=32).start()
        x = np.zeros((1, 4), np.float32)
        outcomes = {"ok": 0, "shed": 0, "deadline": 0, "conn": 0}
        lock = threading.Lock()

        def one(i):
            cli = ServingClient(proxy.endpoint)
            try:
                cli.infer("m", {"x": x}, deadline_ms=400.0,
                          retry_sheds=False)
                key = "ok"
            except ServerOverloaded:
                key = "shed"
            except DeadlineExceeded:
                key = "deadline"
            except Exception:
                key = "conn"
            finally:
                cli.close()
            with lock:
                outcomes[key] += 1

        try:
            boot = ServingClient(server.endpoint)
            boot.load_model("m", md, buckets=[2, 4])
            boot.infer("m", {"x": x})  # warm directly
            set_dispatch_delay(0.15)
            threads = [threading.Thread(target=one, args=(i,))
                       for i in range(24)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
            assert not any(t.is_alive() for t in threads), \
                "requests hung under overload"
            assert sum(outcomes.values()) == 24
            assert outcomes["ok"] >= 1
            assert outcomes["shed"] >= 1, outcomes
            assert boot.stats()["stats"]["models"]["m"]["shed"] >= 1
        finally:
            set_dispatch_delay(0.0)
            proxy.stop()
            server.shutdown(drain=False, timeout=5.0)

    def test_shutdown_drains_inflight_requests(self, tmp_path):
        md = _export_fc(tmp_path, seed=15)
        server = InferenceServer(buckets=(2,), deadline_ms=2).start()
        x = np.zeros((1, 4), np.float32)
        results, errs = [], []
        boot = ServingClient(server.endpoint)
        boot.load_model("m", md, buckets=[2])
        boot.infer("m", {"x": x})
        set_dispatch_delay(0.05)

        def worker():
            cli = ServingClient(server.endpoint)
            try:
                results.append(cli.infer("m", {"x": x},
                                         deadline_ms=60000.0))
            except Exception as e:
                errs.append(e)
            finally:
                cli.close()

        threads = [threading.Thread(target=worker) for _ in range(6)]
        for t in threads:
            t.start()
        time.sleep(0.1)  # let requests land in the queue
        set_dispatch_delay(0.0)
        boot.shutdown_server(drain=True)
        for t in threads:
            t.join(timeout=60)
        assert not errs, errs[:3]
        assert len(results) == 6, \
            "drain-on-shutdown dropped %d in-flight requests" \
            % (6 - len(results))

    def test_unknown_model_and_bad_request_codes(self, tmp_path):
        server = InferenceServer().start()
        cli = ServingClient(server.endpoint)
        try:
            from paddle_tpu.serving import ServingError
            with pytest.raises(ServingError, match="no_model"):
                cli.infer("ghost", {"x": np.zeros((1, 2), np.float32)})
            with pytest.raises(ServingError, match="bad_request"):
                cli._call_once({"cmd": "bogus"})
        finally:
            cli.close()
            server.shutdown(drain=False, timeout=5.0)

    def test_model_root_autoload(self, tmp_path):
        root = tmp_path / "zoo"
        root.mkdir()
        _export_fc(root, seed=16, name="alpha")
        _export_fc(root, seed=17, name="beta")
        server = InferenceServer(model_root=str(root),
                                 buckets=(2,), deadline_ms=1).start()
        cli = ServingClient(server.endpoint)
        try:
            reply = cli.stats()
            assert set(reply["models"]) == {"alpha", "beta"}
            out = cli.infer("beta",
                            {"x": np.zeros((1, 4), np.float32)})[0]
            assert out.shape == (1, 6)
        finally:
            cli.close()
            server.shutdown(drain=True)


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------

class TestMetrics:
    def test_reservoir_histogram_percentiles(self):
        from paddle_tpu.serving import ReservoirHistogram
        h = ReservoirHistogram(capacity=2048)
        for v in range(1, 1001):
            h.record(float(v))
        assert h.count == 1000
        assert abs(h.percentile(50) - 500.5) < 1.0
        assert abs(h.percentile(99) - 990.0) < 2.0
        s = h.summary()
        assert s["min"] == 1.0 and s["max"] == 1000.0

    def test_reservoir_bounded_memory(self):
        from paddle_tpu.serving import ReservoirHistogram
        h = ReservoirHistogram(capacity=64)
        for v in range(10000):
            h.record(v)
        assert len(h._samples) == 64
        assert h.count == 10000
        # sampled percentiles stay in the data's range and ordered
        p50, p95 = h.percentile(50), h.percentile(95)
        assert 0 <= p50 <= p95 <= 9999

    def test_snapshot_is_wire_encodable(self, tmp_path):
        from paddle_tpu.native import wire
        md = _export_fc(tmp_path, seed=18)
        reg = ModelRegistry(deadline_ms=1)
        try:
            reg.load_model("m", md, buckets=(2,))
            reg.infer("m", {"x": np.zeros((1, 4), np.float32)},
                      timeout=60)
            snap = reg.metrics.snapshot()
            decoded = wire.decode(wire.encode(snap))
            assert decoded["models"]["m"]["responses"] == 1
            assert decoded["models"]["m"]["latency_ms"]["count"] == 1
        finally:
            reg.close_all()


# ---------------------------------------------------------------------------
# tools
# ---------------------------------------------------------------------------

def test_bench_serving_smoke_subprocess():
    """Tier-1 CI proof of the whole stack in a fresh process: export,
    serve, open-loop load, JSON lane output."""
    import json
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "bench_serving.py"),
         "--smoke"],
        capture_output=True, text=True, timeout=600, cwd=REPO, env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [l for l in proc.stdout.splitlines() if l.startswith("{")]
    assert lines, proc.stdout[-500:]
    rec = json.loads(lines[-1])
    assert rec["metric"] == "serving_qps"
    assert rec["ok"] > 0 and rec["errors"] == 0
    assert rec["platform"] == "cpu" and rec["device_count"] >= 1


def test_serving_top_renders_stats(tmp_path, capsys):
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import serving_top
    md = _export_fc(tmp_path, seed=19)
    server = InferenceServer(buckets=(2,), deadline_ms=1).start()
    cli = ServingClient(server.endpoint)
    try:
        cli.load_model("demo", md, buckets=[2])
        cli.infer("demo", {"x": np.zeros((1, 4), np.float32)})
        serving_top.main([server.endpoint])
        out = capsys.readouterr().out
        assert "demo" in out and "QPS" in out and "SHED" in out
    finally:
        cli.close()
        server.shutdown(drain=True)
