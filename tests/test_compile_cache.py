"""Persistent compile/artifact cache tests (paddle_tpu/compile_cache —
COMPILE_CACHE.md).

Pins the subsystem's contracts: content-addressed put/get with CRC
verification, silent rejection+recompile of corrupt entries, size-capped
LRU eviction, cross-process reuse (a second boot performs ZERO fresh
compilations for previously-seen (program, bucket, device-kind) triples
— the warm server boot / hot-swap flip acceptance), kill-mid-commit
crash safety (via tools/chaos.py's cache-commit scenario), the repo-wide
kernel-tuning registry with atomic record commits and the legacy JSON
fallback, cache observability through serving metrics / stats / the
load_model reply / serving_top, and the verify_compile_cache CLI.
Everything CPU-safe under JAX_PLATFORMS=cpu.
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
from paddle_tpu import compile_cache as cc
from paddle_tpu.ops import attention_tuning

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)


@pytest.fixture
def store(tmp_path):
    """Point the compile cache at a fresh per-test store and reset the
    process counters; restore the previous flags afterwards."""
    old = fluid.get_flags(["compile_cache", "compile_cache_dir",
                           "compile_cache_max_mb"])
    root = str(tmp_path / "cc_store")
    fluid.set_flags({"compile_cache": True, "compile_cache_dir": root,
                     "compile_cache_max_mb": 1024})
    cc.reset_stats()
    yield root
    fluid.set_flags(old)
    cc.reset_stats()


def _export_fc(tmp_path, seed, name="m", buckets=None):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = seed
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[6], dtype="float32")
        h = fluid.layers.fc(input=x, size=8, act="relu")
        pred = fluid.layers.fc(input=h, size=4, act="softmax")
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        md = str(tmp_path / name)
        fluid.save_inference_model(md, ["x"], [pred], exe,
                                   main_program=main)
    return md


def _predictor(md, buckets=(2, 4)):
    from paddle_tpu.inference import AnalysisConfig, Predictor
    cfg = AnalysisConfig(model_dir=md)
    cfg.batch_size_buckets = tuple(buckets)
    return Predictor(cfg)


# ---------------------------------------------------------------------------
# store primitives: put/get, corruption rejection, eviction
# ---------------------------------------------------------------------------

def test_store_put_get_roundtrip(store):
    s = cc.CompileCache(root=store, xla_cache=False)
    fp = {"kind": "t", "program": "abc", "env": {"jax": "x"}}
    blob = b"executable-bytes" * 10
    assert s.get(fp) is None          # miss on empty store
    path = s.put(fp, blob)
    assert path and os.path.isdir(path)
    assert s.get(fp) == blob          # hit round-trips the bytes
    assert s.get({"kind": "other"}) is None  # different fingerprint
    st = cc.stats()
    assert st["hits"] == 1 and st["misses"] == 2 and st["puts"] == 1
    # committed entry passes verification
    assert [e for _, e, _ in s.verify()] == [None]


def test_fingerprint_key_canonical():
    a = cc.fingerprint_key({"b": 1, "a": [1, 2]})
    b = cc.fingerprint_key({"a": [1, 2], "b": 1})
    assert a == b and len(a) == 64
    assert cc.fingerprint_key({"a": [2, 1], "b": 1}) != a


def test_corrupted_entry_is_silent_miss_and_quarantined(store):
    s = cc.CompileCache(root=store, xla_cache=False)
    fp = {"kind": "t", "program": "corrupt-me"}
    path = s.put(fp, b"Z" * 256)
    # bit-flip the executable
    ep = os.path.join(path, cc.EXEC_NAME)
    raw = bytearray(open(ep, "rb").read())
    raw[len(raw) // 2] ^= 0x10
    open(ep, "wb").write(bytes(raw))
    assert s.get(fp) is None          # rejected, not raised
    assert not os.path.isdir(path)    # quarantined
    assert cc.stats()["errors"] == 1
    # truncation is rejected the same way
    path = s.put(fp, b"Z" * 256)
    with open(os.path.join(path, cc.EXEC_NAME), "wb") as f:
        f.write(b"Z" * 100)
    assert s.get(fp) is None
    # an unparsable manifest is rejected too
    path = s.put(fp, b"Z" * 256)
    with open(os.path.join(path, cc.MANIFEST_NAME), "w") as f:
        f.write("{not json")
    assert s.get(fp) is None
    assert s.entries() == []


def test_eviction_lru_cap(store):
    # cap at 1 MiB; three ~400 KiB entries -> the least-recently-USED
    # one is evicted, never the entry just written
    s = cc.CompileCache(root=store, max_mb=1, xla_cache=False)
    fps = [{"kind": "t", "i": i} for i in range(3)]
    s.put(fps[0], b"a" * 400_000)
    time.sleep(0.02)
    s.put(fps[1], b"b" * 400_000)
    time.sleep(0.02)
    assert s.get(fps[0]) is not None  # touch 0: now 1 is the LRU
    time.sleep(0.02)
    s.put(fps[2], b"c" * 400_000)     # over cap -> evict 1
    assert s.get(fps[1]) is None
    assert s.get(fps[0]) is not None
    assert s.get(fps[2]) is not None
    assert cc.stats()["evictions"] >= 1


# ---------------------------------------------------------------------------
# predictor wiring: cold miss -> warm hit, clone sharing, parity
# ---------------------------------------------------------------------------

def test_predictor_cold_miss_then_warm_hit_bit_exact(store, tmp_path):
    md = _export_fc(tmp_path, seed=5)
    x = np.random.RandomState(0).randn(2, 6).astype(np.float32)
    p1 = _predictor(md)
    out1, = p1.run({"x": x})
    st = cc.stats()
    assert st["misses"] == 1 and st["puts"] == 1 and st["hits"] == 0
    # a FRESH predictor over the same artifact deserializes the stored
    # executable: no retrace, no fresh compile, bit-identical replies
    p2 = _predictor(md)
    out2, = p2.run({"x": x})
    st = cc.stats()
    assert st["hits"] == 1 and st["misses"] == 1
    assert np.array_equal(out1, out2)
    # the cached-executable path is bit-exact vs the legacy direct path
    fluid.set_flags({"compile_cache": False})
    try:
        p3 = _predictor(md)
        out3, = p3.run({"x": x})
    finally:
        fluid.set_flags({"compile_cache": True})
    assert np.array_equal(out1, out3)


def test_clone_to_shares_one_executable(store, tmp_path):
    md = _export_fc(tmp_path, seed=6)
    x = np.zeros((2, 6), np.float32)
    p = _predictor(md)
    out, = p.run({"x": x})
    before = cc.stats()
    # replicas of the same device kind ride the SHARED deserialized
    # executable: zero additional store traffic, zero retraces
    clones = [p.clone_to(None) for _ in range(3)]
    for q in clones:
        oq, = q.run({"x": x})
        assert np.array_equal(out, oq)
        assert q._shared_exports is p._shared_exports
    d = cc.stats_delta(before)
    assert d["hits"] == 0 and d["misses"] == 0 and d["compile_ms"] == 0


def test_registry_hot_swap_flip_zero_fresh_compiles(store, tmp_path):
    from paddle_tpu.serving import ModelRegistry
    md = _export_fc(tmp_path, seed=7)
    reg = ModelRegistry()
    try:
        e1 = reg.load_model("m", md, buckets=(2, 4))
        assert e1.compile_cache["misses"] == 2   # cold: one per bucket
        assert e1.compile_cache["hits"] == 0
        # the hot-swap flip of the same artifact: every (bucket,
        # device-kind) executable comes from the store — ZERO fresh
        # compilations (the autoscaling acceptance pin)
        e2 = reg.load_model("m", md, buckets=(2, 4))
        assert e2.version == e1.version + 1
        assert e2.compile_cache["misses"] == 0
        assert e2.compile_cache["hits"] == 2
        assert e2.compile_cache["compile_ms"] == 0
        # per-model metrics accumulated both loads
        snap = reg.metrics.model("m").snapshot()["compile_cache"]
        assert snap["hits"] == 2 and snap["misses"] == 2
    finally:
        reg.close_all(drain=False, timeout=5.0)


# ---------------------------------------------------------------------------
# observability: load_model reply, stats RPC, serving_top column
# ---------------------------------------------------------------------------

def test_server_surfaces_compile_cache_counters(store, tmp_path, capsys):
    from paddle_tpu.serving import InferenceServer, ServingClient
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import serving_top
    md = _export_fc(tmp_path, seed=8)
    server = InferenceServer(buckets=(2, 4)).start()
    try:
        cli = ServingClient(server.endpoint)
        reply = cli.load_model("m", md, buckets=[2, 4])
        assert reply["compile_cache"]["misses"] == 2
        reply2 = cli.load_model("m", md, buckets=[2, 4])
        assert reply2["compile_cache"]["misses"] == 0
        assert reply2["compile_cache"]["hits"] == 2
        stats = cli.stats()
        m = stats["stats"]["models"]["m"]
        assert m["compile_cache"] == {"hits": 2, "misses": 2,
                                      "compile_ms":
                                      m["compile_cache"]["compile_ms"]}
        assert stats["stats"]["compile_cache"]["puts"] >= 2
        serving_top.main([server.endpoint])
        out = capsys.readouterr().out
        assert "CCH/M" in out and "2/2" in out
        cli.close()
    finally:
        server.shutdown(drain=False, timeout=5.0)


# ---------------------------------------------------------------------------
# cross-process reuse: a second boot performs no compilation at all
# ---------------------------------------------------------------------------

_CHILD = r"""
import os, sys, json, time
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
store, md, out_npz, poison = sys.argv[1], sys.argv[2], sys.argv[3], \
    sys.argv[4] == "poison"
os.environ["PADDLE_TPU_FLAGS_compile_cache_dir"] = store
from paddle_tpu import compile_cache as cc
from paddle_tpu.fluid import functionalizer
if poison:
    # a warm boot must not rebuild/trace the step function AT ALL —
    # only fingerprinting, deserialization, and XLA may run
    def _no_trace(*a, **k):
        raise AssertionError("warm boot must not trace the program")
    functionalizer.build_step_fn = _no_trace
from paddle_tpu.inference import AnalysisConfig, Predictor
cfg = AnalysisConfig(model_dir=md)
cfg.batch_size_buckets = (2, 4)
t0 = time.monotonic()
p = Predictor(cfg)
rng = np.random.RandomState(3)
outs = [p.run({"x": rng.randn(b, 6).astype(np.float32)})[0]
        for b in (2, 4)]
elapsed_ms = (time.monotonic() - t0) * 1000.0
np.savez(out_npz, o0=outs[0], o1=outs[1])
print("RESULT " + json.dumps({"stats": cc.stats(),
                              "elapsed_ms": elapsed_ms}))
"""


def _run_child(store, md, out_npz, poison):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("PADDLE_TPU_FLAGS_compile_cache_dir", None)
    # jax's own cache is placed from outside, as an operator would: a
    # directory of this test's, and no compile too small to keep — so
    # the warm boot skips the XLA compile as well as the trace
    env["JAX_COMPILATION_CACHE_DIR"] = os.path.join(store, "jax")
    env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    proc = subprocess.run(
        [sys.executable, "-c", _CHILD, store, md, out_npz,
         "poison" if poison else "no"],
        capture_output=True, text=True, timeout=300, env=env, cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("RESULT ")][0]
    return json.loads(line[len("RESULT "):])


def test_cross_process_reuse_skips_compilation(store, tmp_path):
    """The tentpole acceptance: a SECOND process booting the same model
    over the same store performs zero fresh compilations (hit counters)
    and never traces (build_step_fn poisoned), with bit-identical
    replies and a warm boot at least as fast as the cold one."""
    md = _export_fc(tmp_path, seed=9)
    cold = _run_child(store, md, str(tmp_path / "cold.npz"),
                      poison=False)
    assert cold["stats"]["misses"] == 2
    assert cold["stats"]["puts"] == 2
    warm = _run_child(store, md, str(tmp_path / "warm.npz"),
                      poison=True)
    assert warm["stats"]["hits"] == 2
    assert warm["stats"]["misses"] == 0
    assert warm["stats"]["compile_ms"] == 0
    # wall-clock sanity: skipping trace+lower+compile cannot be slower
    assert warm["elapsed_ms"] < cold["elapsed_ms"], \
        "warm boot %.1fms not faster than cold %.1fms" \
        % (warm["elapsed_ms"], cold["elapsed_ms"])
    a = np.load(str(tmp_path / "cold.npz"))
    b = np.load(str(tmp_path / "warm.npz"))
    assert np.array_equal(a["o0"], b["o0"])
    assert np.array_equal(a["o1"], b["o1"])


# ---------------------------------------------------------------------------
# crash safety: SIGKILL mid-commit never corrupts the store
# ---------------------------------------------------------------------------

def test_kill_mid_cache_commit_recovers(tmp_path):
    """tools/chaos.py cache-commit scenario (deterministic exit at the
    cc_exec_written point): the interrupted commit leaves only a stale
    tmp next to the intact first entry; the next boot serves the same
    bits, recompiles ONLY the interrupted entry, sweeps the tmp."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import chaos
    st = chaos.scenario_cache_commit(str(tmp_path), real_kill=False,
                                     verbose=False)
    assert st["hits"] == 1 and st["misses"] == 1


# ---------------------------------------------------------------------------
# kernel-tuning registry
# ---------------------------------------------------------------------------

def test_tuning_registry_roundtrip_and_store_layout(store):
    path = cc.tuning_record("flash_attention", "S128_D64_c1_bfloat16",
                            {"block_q": 64, "block_kv": 64})
    assert path.startswith(store)
    assert cc.tuning_lookup("flash_attention",
                            "S128_D64_c1_bfloat16")["block_q"] == 64
    assert cc.tuning_lookup("flash_attention", "nope") is None
    # a second record merges (read-modify-write), does not clobber
    cc.tuning_record("flash_attention", "S256_D64_c0_float32",
                     {"block_q": 128, "block_kv": 128})
    assert len(cc.tuning_entries("flash_attention")) == 2
    with pytest.raises(ValueError):
        cc.tuning_path("../escape")


def test_attention_tuning_rides_registry(store):
    """With no legacy override, attention_tuning records into and reads
    from the repo-wide registry namespace."""
    old = fluid.get_flags(["attention_tune_cache"])
    fluid.set_flags({"attention_tune_cache": ""})
    try:
        cfg = attention_tuning.AttentionConfig(32, 64, 16, 32)
        path = attention_tuning.record(512, 64, True, "bfloat16", cfg)
        assert path == cc.tuning_path(attention_tuning.TUNING_NAMESPACE)
        assert attention_tuning.lookup(512, 64, True, "bfloat16") == cfg
        assert attention_tuning.lookup(512, 64, False, "bfloat16") is None
    finally:
        fluid.set_flags(old)


def test_attention_tuning_legacy_json_read_only_fallback(
        store, tmp_path, monkeypatch):
    """A pre-registry tune JSON at the legacy default path still
    resolves (read-only) when the registry has no entry; a registry
    entry for the same key wins."""
    old = fluid.get_flags(["attention_tune_cache"])
    fluid.set_flags({"attention_tune_cache": ""})
    legacy = str(tmp_path / "legacy_tune.json")
    with open(legacy, "w") as f:
        json.dump({"S1024_D64_c1_bfloat16":
                   {"block_q": 8, "block_kv": 8}}, f)
    monkeypatch.setattr(attention_tuning, "cache_path", lambda: legacy)
    try:
        got = attention_tuning.lookup(1024, 64, True, "bfloat16")
        assert got == attention_tuning.AttentionConfig(8, 8)
        # registry beats legacy for the same key
        attention_tuning.record(
            1024, 64, True, "bfloat16",
            attention_tuning.AttentionConfig(16, 16))
        got = attention_tuning.lookup(1024, 64, True, "bfloat16")
        assert got == attention_tuning.AttentionConfig(16, 16)
        # the legacy file was never rewritten
        with open(legacy) as f:
            assert json.load(f)["S1024_D64_c1_bfloat16"]["block_q"] == 8
    finally:
        fluid.set_flags(old)


def test_tuning_record_atomic_under_kill(store, tmp_path):
    """A tuner killed between the durable temp write and the rename
    (chaos point tuning_tmp_written) leaves the PREVIOUS registry
    intact — never a truncated JSON that poisons later traces.  Covers
    both the registry path and the legacy FLAGS-pinned path."""
    from paddle_tpu.fluid import checkpoint as ckpt

    class Boom(RuntimeError):
        pass

    def bomb(point):
        if point == "tuning_tmp_written":
            raise Boom(point)

    # registry path
    cc.tuning_record("flash_attention", "k1", {"block_q": 64,
                                               "block_kv": 64})
    ckpt.set_chaos_hook(bomb)
    try:
        with pytest.raises(Boom):
            cc.tuning_record("flash_attention", "k2", {"block_q": 128,
                                                       "block_kv": 128})
    finally:
        ckpt.set_chaos_hook(None)
    ents = cc.tuning_entries("flash_attention")
    assert ents.get("k1", {}).get("block_q") == 64 and "k2" not in ents

    # legacy path (FLAGS.attention_tune_cache override)
    legacy = str(tmp_path / "tune.json")
    old = fluid.get_flags(["attention_tune_cache"])
    fluid.set_flags({"attention_tune_cache": legacy})
    try:
        cfg = attention_tuning.AttentionConfig(32, 32)
        attention_tuning.record(64, 64, False, "float32", cfg)
        ckpt.set_chaos_hook(bomb)
        try:
            with pytest.raises(Boom):
                attention_tuning.record(
                    128, 64, False, "float32",
                    attention_tuning.AttentionConfig(64, 64))
        finally:
            ckpt.set_chaos_hook(None)
        with open(legacy) as f:
            data = json.load(f)
        assert "S64_D64_c0_float32" in data      # old record intact
        assert "S128_D64_c0_float32" not in data  # aborted one absent
        assert attention_tuning.lookup(64, 64, False, "float32") == cfg
    finally:
        fluid.set_flags(old)


# ---------------------------------------------------------------------------
# verify_compile_cache CLI
# ---------------------------------------------------------------------------

def test_verify_compile_cache_cli(store, capsys):
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import verify_compile_cache
    s = cc.CompileCache(root=store, xla_cache=False)
    fp = {"kind": "t", "program": "cli"}
    path = s.put(fp, b"E" * 512)
    cc.tuning_record("flash_attention", "k", {"block_q": 8,
                                              "block_kv": 8})
    assert verify_compile_cache.main([store]) == 0
    out = capsys.readouterr().out
    assert "OK" in out and "tuning/flash_attention.json" in out
    # corrupt the entry: exit 2, message NAMES it
    ep = os.path.join(path, cc.EXEC_NAME)
    raw = bytearray(open(ep, "rb").read())
    raw[0] ^= 0xFF
    open(ep, "wb").write(bytes(raw))
    assert verify_compile_cache.main([store]) == 2
    err = capsys.readouterr().err
    assert os.path.basename(path) in err and "CRC32" in err
    # empty root: exit 1
    assert verify_compile_cache.main([store + "_nope"]) == 1


# ---------------------------------------------------------------------------
# executor inference-side compile cache (opt-in flag)
# ---------------------------------------------------------------------------

def test_executor_compile_cache_inference_program(store):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 4
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[6], dtype="float32")
        pred = fluid.layers.fc(input=x, size=3, act="softmax")
    xv = np.random.RandomState(2).randn(2, 6).astype(np.float32)
    # baseline: flag off
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        ref, = exe.run(main, feed={"x": xv}, fetch_list=[pred])
    fluid.set_flags({"executor_compile_cache": True})
    try:
        with fluid.scope_guard(fluid.Scope()):
            exe1 = fluid.Executor(fluid.CPUPlace())
            exe1.run(startup)
            before = cc.stats()
            out1, = exe1.run(main, feed={"x": xv}, fetch_list=[pred])
            d1 = cc.stats_delta(before)
            assert d1["misses"] >= 1 and d1["puts"] >= 1
            # a FRESH executor on the same program rides the store
            exe2 = fluid.Executor(fluid.CPUPlace())
            before = cc.stats()
            out2, = exe2.run(main, feed={"x": xv}, fetch_list=[pred])
            d2 = cc.stats_delta(before)
            assert d2["hits"] >= 1 and d2["misses"] == 0
        assert np.array_equal(ref, out1) and np.array_equal(out1, out2)
    finally:
        fluid.set_flags({"executor_compile_cache": False})


def test_executor_compile_cache_skips_training_programs(store):
    """A program with grad/optimizer ops must NOT ride the export path
    (donation, in-place update semantics) — the gate filters it out and
    the store stays untouched."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 4
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[4], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="float32")
        p = fluid.layers.fc(input=x, size=1)
        loss = fluid.layers.mean(
            fluid.layers.square_error_cost(p, y))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    xv = np.ones((2, 4), np.float32)
    yv = np.ones((2, 1), np.float32)
    fluid.set_flags({"executor_compile_cache": True})
    try:
        with fluid.scope_guard(fluid.Scope()):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            before = cc.stats()
            l1, = exe.run(main, feed={"x": xv, "y": yv},
                          fetch_list=[loss])
            l2, = exe.run(main, feed={"x": xv, "y": yv},
                          fetch_list=[loss])
            d = cc.stats_delta(before)
            assert not exe._aot_cache_eligible(main)
            # the training program never touched the store (the startup
            # program legitimately may)
            assert float(l2) < float(l1)
    finally:
        fluid.set_flags({"executor_compile_cache": False})


# ---------------------------------------------------------------------------
# jax's own persistent cache is placed from outside the program
# ---------------------------------------------------------------------------

_BUILDERS = {
    "compile_cache": lambda root: cc.CompileCache(root=root),
    "executor": lambda root: fluid.Executor(fluid.CPUPlace()),
}


@pytest.fixture
def jax_cache_config():
    """Run with jax's cache directory unconfigured; restore it
    afterwards."""
    import jax
    prev_dir = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", None)
    yield jax.config
    jax.config.update("jax_compilation_cache_dir", prev_dir)


@pytest.mark.parametrize("builder", sorted(_BUILDERS))
def test_jax_cache_dir_env_set_is_left_alone(store, jax_cache_config,
                                             monkeypatch, builder):
    """JAX_COMPILATION_CACHE_DIR set: jax keeps its cache there and no
    code of ours touches jax's config — neither a CompileCache (the
    predictors) nor an Executor (the trainers)."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    _BUILDERS[builder](store)
    assert jax_cache_config.jax_compilation_cache_dir is None
    assert cc.ensure_jax_cache() == "/some/dir"


@pytest.mark.parametrize("builder", sorted(_BUILDERS))
def test_jax_cache_dir_env_unset_is_fixed_checkout_path(
        store, jax_cache_config, monkeypatch, builder):
    """Unset: the one setter points jax at <checkout>/.cache/jax — a path
    that never moves with FLAGS.compile_cache_dir (the `store` fixture
    repoints the AOT store to a temp dir), a pid or the time."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    _BUILDERS[builder](store)
    want = os.path.join(REPO, ".cache", "jax")
    assert jax_cache_config.jax_compilation_cache_dir == want
    assert cc.ensure_jax_cache() == want
    assert cc.cache_root() == store != os.path.dirname(want)


def test_default_store_root_is_inside_the_checkout():
    old = fluid.get_flags(["compile_cache_dir"])
    fluid.set_flags({"compile_cache_dir": ""})
    try:
        assert cc.cache_root() == os.path.join(REPO, ".cache",
                                               "paddle_tpu")
    finally:
        fluid.set_flags(old)


@pytest.mark.parametrize("script", ["chip_smoke.py", "bench.py"])
def test_no_chip_means_no_result(script):
    """Without an accelerator the chip check and the benchmark exit
    non-zero and print neither an `"ok": true` line nor a metric — they
    never fall back to the CPU."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, os.path.join(REPO, script)],
                          capture_output=True, text=True, timeout=300,
                          cwd=REPO, env=env)
    assert proc.returncode not in (0, None), proc.stdout[-500:]
    assert '"ok"' not in proc.stdout and '"metric"' not in proc.stdout
    assert "tpu" in proc.stderr
